from perf import flops_nemotron_h, stamps, trace_reduce


def read(run, params):
    trace, c = run["trace"], run["counters"]
    if (not trace or not run["peaks"] or not c.get("decode_steps")
            or not c.get("decode_moe_steps_total")):
        return None
    # the slice may cut the program's first and last run: leave both out
    runs = sorted(trace_reduce.matching(trace["modules"], params["module"]),
                  key=lambda e: e[1])[1:-1]
    if not runs:
        return None
    step_s = sum(e[2] for e in runs) / len(runs) / 1e9
    attended, _tokens = stamps.positions_attended(run["stamped"]["spans"])
    live_kv = attended / c["decode_steps"]
    live_slots = c["occupancy_sum"] / c["decode_steps"]
    # held experts with a live token, summed over the expert layers, a
    # decode step: the counter's mean, not every expert held
    active = c["decode_experts_active_total"] / c["decode_moe_steps_total"]
    least_s = (flops_nemotron_h.decode_step_bytes(
        run["dims"], active, live_slots, live_kv)
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["hybrid_decode_roofline"] = {
        "runs": len(runs), "device_ms_a_run": 1e3 * step_s,
        "least_ms_a_run": 1e3 * least_s, "experts_active_a_step": active,
        "live_slots_a_step": live_slots,
        "positions_attended_a_step": live_kv}
    return 100.0 * least_s / step_s
