"""A kernel's calls inside a program's runs, from a reduced trace."""

from perf import trace_reduce


def under(trace: dict, module: str, kernel: str) -> tuple[int, int]:
    """(runs, device ns): the runs of the program named ``module`` that
    the slice holds whole (its first and last left out: the slice may
    cut them), and the summed duration of the custom calls named
    ``kernel`` (a pallas call's ``name=``: ``%<kernel>.N = ...
    custom-call(...)``) that start inside them."""
    runs = sorted(trace_reduce.matching(trace["modules"], module),
                  key=lambda e: e[1])[1:-1]
    spans = [(start, start + dur) for _name, start, dur in runs]
    total = 0
    for name, start, dur in trace["ops"]:
        if (name.lstrip("%").startswith(kernel)
                and " custom-call(" in name
                and any(lo <= start < hi for lo, hi in spans)):
            total += dur
    return len(runs), total
