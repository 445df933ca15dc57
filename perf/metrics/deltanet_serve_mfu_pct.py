from perf import flops_qwen3_next, stamps


def read(run, params):
    spans, c = run["stamped"]["spans"], run["counters"]
    if (not run["peaks"] or not spans
            or "expert_assignments_held_total" not in c):
        return None
    positions, tokens = stamps.positions_attended(spans)
    for s in spans:
        if s["prefill"]:        # no prefix is adopted: the whole prompt
            n = s["prompt_len"]
            tokens += n
            positions += n * (n + 1) / 2.0
    work = flops_qwen3_next.serve_flops(
        run["dims"], tokens, c["expert_assignments_held_total"], positions)
    run["notes"]["deltanet_serve_mfu_pct"] = {
        "tokens_fed": tokens, "positions_attended": positions,
        "expert_assignments_held": c["expert_assignments_held_total"],
        "flops": work}
    return 100.0 * work / (run["window_s"]
                           * run["peaks"]["bf16_flops_per_s"])
