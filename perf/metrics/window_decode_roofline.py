from perf import flops_afmoe, kernel_calls


def read(run, params):
    trace, c = run["trace"], run["counters"]
    steps = c.get("decode_moe_steps_total")
    if (not trace or not run["peaks"] or not steps
            or "decode_full_positions_total" not in c):
        return None
    runs, ns = 0, 0
    for kernel in params["kernels"]:
        runs, got = kernel_calls.under(trace, params["module"], kernel)
        ns += got
    if not runs or not ns:
        return None
    call_s = ns / runs / 1e9
    # keys a layer of each kind attended a decode step, summed over the
    # live slots: the program's own counters over the window
    full = c["decode_full_positions_total"] / steps
    window = c["decode_window_positions_total"] / steps
    least_s = (flops_afmoe.decode_attention_bytes(run["dims"], full, window)
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["window_decode_roofline"] = {
        "runs": runs, "kernels_ms_a_run": 1e3 * call_s,
        "least_ms_a_run": 1e3 * least_s,
        "full_positions_a_step": full, "window_positions_a_step": window}
    return 100.0 * least_s / call_s
