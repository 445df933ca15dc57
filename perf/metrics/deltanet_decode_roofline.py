from perf import flops_qwen3_next, kernel_calls


def read(run, params):
    trace, c = run["trace"], run["counters"]
    if not trace or not run["peaks"] or not c.get("decode_steps"):
        return None
    runs, ns = kernel_calls.under(trace, params["module"], params["kernel"])
    if not runs or not ns:
        return None
    call_s = ns / runs / 1e9
    live = c["occupancy_sum"] / c["decode_steps"]
    least_s = (flops_qwen3_next.deltanet_decode_bytes(run["dims"], live)
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["deltanet_decode_roofline"] = {
        "runs": runs, "kernel_ms_a_run": 1e3 * call_s,
        "least_ms_a_run": 1e3 * least_s, "live_slots_a_step": live}
    return 100.0 * least_s / call_s
