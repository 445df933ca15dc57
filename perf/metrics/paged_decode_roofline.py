from perf import flops, trace_reduce


def read(run, params):
    trace, c = run["trace"], run["counters"]
    if not trace or not run["peaks"] or not c.get("decode_steps"):
        return None
    # the slice may cut the program's first and last run: leave both out
    runs = sorted(trace_reduce.matching(trace["modules"], params["module"]),
                  key=lambda e: e[1])[1:-1]
    if not runs:
        return None
    step_s = sum(e[2] for e in runs) / len(runs) / 1e9
    # positions attended, summed over the decode steps of the requests
    # the window finished, a step: token j of an answer attends the
    # prompt and the j before it
    attended = sum(n * r["prompt_len"] + n * (n - 1) / 2.0
                   for r in run["in_window"]
                   for n in [len(r["tokens"])])
    live = attended / c["decode_steps"]
    least_s = (flops.decode_step_bytes(run["dims"], live)
               / run["peaks"]["hbm_bytes_per_s"])
    run["notes"]["paged_decode_roofline"] = {
        "runs": len(runs), "device_ms_a_run": 1e3 * step_s,
        "least_ms_a_run": 1e3 * least_s, "positions_attended_a_step": live}
    return 100.0 * least_s / step_s
