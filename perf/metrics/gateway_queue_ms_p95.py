from perf import stamps
from perf.traffic_gen import percentile


def read(run, params):
    """From each whole answer's own stamps: ``t_admitted`` less the
    start of its ``submit_and_wait`` call."""
    waits = stamps.waits(run["requests"], run["t0"])["queue_ms"]
    return percentile(waits, params["quantile"]) if waits else None
