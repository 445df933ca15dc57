from perf.way_back import read  # noqa: F401
