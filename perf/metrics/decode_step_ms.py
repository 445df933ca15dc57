def read(run, params):
    steps = run["counters"].get("decode_steps")
    if not steps:
        return None
    return 1e3 * run["window_s"] / steps
