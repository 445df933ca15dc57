from perf import flops_lfm2, trace_reduce


def read(run, params):
    trace, c = run["trace"], run["counters"]
    steps = c.get("decode_moe_steps_total")
    if (not trace or not run["peaks"] or not c.get("decode_steps")
            or not steps or "decode_full_positions_total" not in c):
        return None
    # the slice may cut the program's first and last run: leave both out
    runs = sorted(trace_reduce.matching(trace["modules"], params["module"]),
                  key=lambda e: e[1])[1:-1]
    if not runs:
        return None
    step_s = sum(e[2] for e in runs) / len(runs) / 1e9
    live = c["occupancy_sum"] / c["decode_steps"]
    # experts that met a live token, summed over the expert layers, and
    # the keys one attention layer attended, a decode step: the
    # program's own counters over the window
    active = c["decode_experts_active_total"] / steps
    pairs = c["decode_full_positions_total"] / steps
    least_s = (flops_lfm2.decode_step_bytes(run["dims"], active, live, pairs)
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["shortconv_decode_roofline"] = {
        "runs": len(runs), "device_ms_a_run": 1e3 * step_s,
        "least_ms_a_run": 1e3 * least_s, "experts_active_a_step": active,
        "live_slots_a_step": live, "pairs_a_step": pairs}
    return 100.0 * least_s / step_s
