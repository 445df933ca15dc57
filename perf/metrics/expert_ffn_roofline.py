from perf import flops_qwen3_next, kernel_calls


def read(run, params):
    trace, c = run["trace"], run["counters"]
    if (not trace or not run["peaks"]
            or not c.get("decode_moe_steps_total")):
        return None
    runs, ns = kernel_calls.under(trace, params["module"], params["kernel"])
    if not runs or not ns:
        return None
    call_s = ns / runs / 1e9
    # held experts with a live token, summed over the expert layers, a
    # decode step: the counter's mean, not every expert held
    active = c["decode_experts_active_total"] / c["decode_moe_steps_total"]
    least_s = (active * flops_qwen3_next.expert_bytes(run["dims"])
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["expert_ffn_roofline"] = {
        "runs": runs, "kernel_ms_a_run": 1e3 * call_s,
        "least_ms_a_run": 1e3 * least_s, "experts_active_a_step": active}
    return 100.0 * least_s / call_s
