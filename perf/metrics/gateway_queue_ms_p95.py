from perf.traffic_gen import percentile


def read(run, params):
    """The n-th request sent (by the time it was sent) is seated when
    the engine's count of admissions reaches the count before the
    window plus n. Only where nothing was shed does the ordinal hold."""
    sent = sorted(r["sent_s"] for r in run["requests"]
                  if r["sent_s"] is not None)
    admits = run.get("admits") or []
    if not sent or not admits or any(r["error"] for r in run["requests"]):
        return None
    waits, j = [], 0
    for n, s in enumerate(sent, start=1):
        want = run["admitted_base"] + n
        while j < len(admits) and admits[j][1] < want:
            j += 1
        if j == len(admits):
            break
        waits.append(1e3 * max(0.0, admits[j][0] - run["t0"] - s))
    return percentile(waits, params["quantile"]) if waits else None
