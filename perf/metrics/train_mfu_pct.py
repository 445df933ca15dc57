from perf import flops


def read(run, params):
    if not run["peaks"] or not run.get("train_tok_s"):
        return None
    per_token = flops.train_flops_per_token(run["dims"], run["seq_len"])
    return (100.0 * per_token * run["train_tok_s"]
            / run["peaks"]["bf16_flops_per_s"])
