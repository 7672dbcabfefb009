"""Plain numpy references of the benchmarked pipelines."""
