from perf.trace_reduce import idle_pct


def read(run, params):
    return idle_pct(run["trace"])
