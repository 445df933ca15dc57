from perf.span_reduce import read_step_idle as read  # noqa: F401
