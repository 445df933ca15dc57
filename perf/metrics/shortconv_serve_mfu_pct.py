from perf import flops_lfm2


def read(run, params):
    spans, c, d = run["stamped"]["spans"], run["counters"], run["dims"]
    if (not run["peaks"] or not spans or "K" not in d
            or "full_positions_total" not in c
            or "expert_assignments_held_total" not in c):
        return None
    f = flops_lfm2.fed(spans)
    work = flops_lfm2.serve_flops(
        d, f["tokens"], f["heads"], c["expert_assignments_held_total"],
        c["full_positions_total"])
    run["notes"]["shortconv_serve_mfu_pct"] = {
        **f, "expert_assignments_held": c["expert_assignments_held_total"],
        "pairs_a_layer": c["full_positions_total"], "flops": work,
        "window_s": run["window_s"]}
    return 100.0 * work / (run["window_s"]
                           * run["peaks"]["bf16_flops_per_s"])
