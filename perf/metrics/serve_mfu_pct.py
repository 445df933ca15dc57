from perf import stamps


def read(run, params):
    spans = run["stamped"]["spans"]
    if not run["peaks"] or not spans:
        return None
    c = run["counters"]
    fresh = 1.0
    if c.get("prompt_tokens"):
        fresh = 1.0 - c["prefix_hit_tokens"] / c["prompt_tokens"]
    work = stamps.forward_flops(run["dims"], spans, fresh)
    return 100.0 * work / (run["window_s"]
                           * run["peaks"]["bf16_flops_per_s"])
