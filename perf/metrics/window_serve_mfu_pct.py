from perf import flops_afmoe


def read(run, params):
    spans, c = run["stamped"]["spans"], run["counters"]
    d = run["dims"]
    if (not run["peaks"] or not spans or "window" not in d
            or "expert_assignments_held_total" not in c):
        return None
    a = flops_afmoe.attended(spans, d["window"])
    work = flops_afmoe.serve_flops(
        d, a["tokens"], a["heads"], c["expert_assignments_held_total"],
        a["full_positions"], a["window_positions"])
    run["notes"]["window_serve_mfu_pct"] = {
        **a, "expert_assignments_held": c["expert_assignments_held_total"],
        "flops": work, "window_s": run["window_s"]}
    return 100.0 * work / (run["window_s"]
                           * run["peaks"]["bf16_flops_per_s"])
