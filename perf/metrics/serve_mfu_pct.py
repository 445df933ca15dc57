from perf import flops


def read(run, params):
    if not run["peaks"] or not run["in_window"]:
        return None
    c = run["counters"]
    fresh = 1.0
    if c.get("prompt_tokens"):
        fresh = 1.0 - c["prefix_hit_tokens"] / c["prompt_tokens"]
    work = sum(flops.serve_flops(run["dims"], r["prompt_len"],
                                 len(r["tokens"]),
                                 round(fresh * r["prompt_len"]))
               for r in run["in_window"])
    return 100.0 * work / (run["window_s"]
                           * run["peaks"]["bf16_flops_per_s"])
