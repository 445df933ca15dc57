import re

from perf import flops, trace_reduce


def read(run, params):
    """Over the whole steps of the traced slice (the step program's
    runs but the first and the last, which the slice cuts): the least
    time the chip could take for the flash kernels' calls, over the
    device time of their events.

    The pallas kernels are the custom calls with operands whose target
    is ``tpu_custom_call`` (the step holds some forty other custom
    calls, allocations and layout marks of no duration: counted as
    kernels they tripled the share, my chip runs, PR 29). A forward
    call writes the log-sum-exp in float32 beside its output, the two
    backward kernels (dq; dk and dv) write bfloat16 only: that tells
    them apart. A step has to hold ``forward_runs_a_layer`` forward
    events (the forward pass's, and the one the backward runs again
    under full rematerialisation) and ``backward_kernels_a_call``
    backward events for each layer and microbatch; where it holds
    another number the reader says so and reports nothing, so that a
    miscount cannot scale the share. The arithmetic of each step goes
    to the run's ``info``."""
    trace, note = run["trace"], run["notes"].setdefault(
        "flash_attn_roofline", {})
    if not trace or not run["peaks"]:
        return None
    steps = sorted(trace_reduce.matching(trace["modules"], params["module"]),
                   key=lambda e: e[1])[1:-1]
    pairs = run["attended_pairs_whole_steps"]
    if not steps or len(steps) != len(pairs):
        note["silent"] = (f"{len(steps)} whole runs of {params['module']} "
                          f"in the slice, {len(pairs)} expected")
        return None
    kernel = re.compile(params["kernel"])
    calls = [e for e in trace["ops"]
             if params["target"] in e[0] and kernel.search(e[0])]
    layers_x_micro = run["dims"]["L"] * run["grad_accum"]
    want = (params["forward_runs_a_layer"] * layers_x_micro,
            params["backward_kernels_a_call"] * layers_x_micro)
    peak_f = run["peaks"]["bf16_flops_per_s"]
    peak_b = run["peaks"]["hbm_bytes_per_s"]
    rows, seq = run["microbatch_rows"], run["seq_len"]
    least = spent = 0.0
    note["steps"] = []
    for (_name, start, dur), p in zip(steps, pairs):
        inside = [e for e in calls
                  if start <= e[1] and e[1] + e[2] <= start + dur]
        fwd = [e for e in inside if params["forward_writes"]
               in kernel.split(e[0].partition(" = ")[2])[0]]
        got = (len(fwd), len(inside) - len(fwd))
        if got != want:
            note["silent"] = (f"a step holds {got} forward and backward "
                              f"kernel events, {want} expected")
            return None
        f, b = flops.flash_call_cost(run["dims"], rows, seq, p,
                                     backward=False)
        fwd_s = max(f / peak_f, b / peak_b)
        f, b = flops.flash_call_cost(run["dims"], rows, seq, p,
                                     backward=True)
        bwd_s = max(f / peak_f, b / peak_b)
        step_least = (got[0] * fwd_s
                      + got[1] / params["backward_kernels_a_call"] * bwd_s)
        step_spent = sum(e[2] for e in inside) / 1e9
        note["steps"].append({
            "forward_events": got[0], "backward_events": got[1],
            "pairs_share_of_causal": p / (seq * (seq + 1) / 2.0),
            "least_ms_a_forward": 1e3 * fwd_s,
            "least_ms_a_backward": 1e3 * bwd_s,
            "least_ms": 1e3 * step_least, "spent_ms": 1e3 * step_spent,
            "step_ms": dur / 1e6})
        least += step_least
        spent += step_spent
    return 100.0 * least / spent if spent else None
