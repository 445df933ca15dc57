from perf import flops, stamps, trace_reduce


def read(run, params):
    trace, c = run["trace"], run["counters"]
    if not trace or not run["peaks"] or not c.get("decode_steps"):
        return None
    # the slice may cut the program's first and last run: leave both out
    runs = sorted(trace_reduce.matching(trace["modules"], params["module"]),
                  key=lambda e: e[1])[1:-1]
    if not runs:
        return None
    step_s = sum(e[2] for e in runs) / len(runs) / 1e9
    # positions attended by the decode tokens stamped inside the
    # window (answers in flight at the close among them), over the
    # window's decode steps: the positions a step's live slots attend
    attended, _tokens = stamps.positions_attended(run["stamped"]["spans"])
    live = attended / c["decode_steps"]
    least_s = (flops.decode_step_bytes(run["dims"], live)
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["paged_decode_roofline"] = {
        "runs": len(runs), "device_ms_a_run": 1e3 * step_s,
        "least_ms_a_run": 1e3 * least_s, "positions_attended_a_step": live}
    return 100.0 * least_s / step_s
