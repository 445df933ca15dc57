import bisect
import re

from perf import flops_afmoe, span_reduce, trace_reduce

#: a flash forward's first output, (heads, T, head_dim) in bf16
FIRST_OUT = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


def bucket(n: int) -> int:
    """The prefill's padding rule: the next power of two >= n."""
    return 1 << max(n - 1, 0).bit_length()


def admits_on_the_trace_clock(run, params):
    """[(ns, prompt length)] of every request the window admitted, its
    ``t_admitted`` stamp (``time.perf_counter``) put on the trace's host
    clock by the kind's mark: a span ``params["mark"]`` entered at the
    instant ``run["clock_mark_s"]``. None where either is missing."""
    marks = [e for e in trace_reduce.matching(run["trace"]["host"],
                                              params["mark"])
             if e[0] == params["mark"]]
    if not marks or run.get("clock_mark_s") is None:
        return None
    offset = marks[0][1] - round(run["clock_mark_s"] * 1e9)
    return sorted((round(r["timeline"]["t_admitted"] * 1e9) + offset,
                   r["prompt_len"])
                  for r in run["requests"]
                  if r.get("timeline") and r["timeline"].get("t_admitted"))


def pair_runs(starts, admits, slack_ns):
    """Index into ``admits`` of each run's request, or None. The engine
    admits one prefill at a time and the device runs them in the order
    they came, so the k-th run is the k-th admit: a run's request is the
    latest admitted before it started (``slack_ns`` for the clocks'
    disagreement), and each earlier run's is an earlier one."""
    times = [a for a, _n in admits]
    out, below = [], len(admits)
    for start in sorted(starts, reverse=True):
        k = min(bisect.bisect_right(times, start + slack_ns) - 1, below - 1)
        out.append(k if k >= 0 else None)
        below = k if k >= 0 else below
    return out[::-1]


def read(run, params):
    trace, d = run["trace"], run["dims"]
    note = run["notes"].setdefault("window_prefill_roofline", {})
    if not trace or not run["peaks"] or "window" not in d:
        return None
    admits = admits_on_the_trace_clock(run, params)
    if not admits:
        note["silent"] = "no clock mark or no admitted request"
        return None
    kinds = [c for c in d["pattern"] if c in "WF"]
    calls = [e for e in trace["ops"] if params["target"] in e[0]]
    peak_f = run["peaks"]["bf16_flops_per_s"]
    peak_b = run["peaks"]["hbm_bytes_per_s"]
    whole = []
    for _name, start, dur in sorted(trace_reduce.matching(
            trace["modules"], params["module"]), key=lambda e: e[1]):
        inside = sorted((e for e in calls
                         if start <= e[1] and e[1] + e[2] <= start + dur),
                        key=lambda e: e[1])
        flash = []
        for text, _at, ns in inside:
            out = text.partition(" = ")[2].partition(" custom-call(")[0]
            m = FIRST_OUT.search(out)
            if m and (int(m[1]), int(m[3])) == (d["H"], d["hd"]):
                flash.append((int(m[2]), ns))
        # a run the slice cut holds fewer calls than attention layers
        if len(flash) == len(kinds):
            whole.append((start, flash))
    # the device's times on the host's clock, by the decode launches
    shift = span_reduce.clock_shift(trace["modules"], trace["host"],
                                    params["launch"])
    which = pair_runs([s + shift for s, _f in whole], admits,
                      params["slack_ns"])
    least = spent = 0.0
    note.update({"runs": [], "unpaired": 0, "device_clock_shift_ns": shift})
    for (start, flash), k in zip(whole, which):
        T = flash[0][0]
        if k is None or bucket(admits[k][1]) != T:
            note["unpaired"] += 1
            continue
        at, n = admits[k]
        run_least = sum(
            max(f / peak_f, b / peak_b) for f, b in (
                flops_afmoe.prefill_attention_cost(d, n, kind == "W")
                for kind in kinds))
        run_spent = sum(ns for _T, ns in flash) / 1e9
        note["runs"].append({"bucket": T, "prompt_len": n,
                             "after_admit_ms": (start + shift - at) / 1e6,
                             "least_ms": 1e3 * run_least,
                             "spent_ms": 1e3 * run_spent})
        least += run_least
        spent += run_spent
    if not spent:
        note["silent"] = ("no whole prefill run with its flash calls "
                          "paired with the request it served")
        return None
    return 100.0 * least / spent
