from perf.span_reduce import read_host_per_step as read  # noqa: F401
