"""setup_s: process start to window start (JAX init, imports, plan,
compile or cache load, warm-up, frame pool), host clock."""


def read(run):
    return run.setup_s
