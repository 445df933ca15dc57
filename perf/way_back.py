"""The host's way back, span by span: of the device's idle time over
the engine's whole steps, what the host spends walking the live slots
(``engine.pick.walk``), preparing the decode call
(``engine.dispatch.prepare``) and inside jax's dispatch of it
(``engine.dispatch.launch``), and the rest, ``wait``: the transfer, the
host's wake and the program's start after the call. Works on plain
lists of events, as ``span_reduce`` does; ``read`` at the end is what
the four ``way_back_ms.*`` readers call with a run.

Why the first three need no clock: the programs run in order on one
stream, so once the fetch of the pick programs' tokens has returned,
every program queued before it has finished, and nothing is queued
again until the launch. The walk and the preparation are idle time on
any clock; so is the launch, but for the part of it the program may
already run, which the bracket below bounds. ``wait`` is the rest of
the idle time over the same whole steps as ``step_idle_ms`` (its
periods, its steps), a total the device's clock moves only at the
slice's two ends.

The bracket: each step gives the device's clock two causal anchors.
A decode program cannot start before its launch call began
(``shift >= launch begin - run start``), and the host cannot hold the
tokens before the pick program produced them
(``shift <= fetch end - pick run end``). ``shift`` is what
``span_reduce.clock_shift`` returns: nanoseconds to add to the
device's times.
"""

import bisect
import statistics

from perf import trace_reduce
from perf.span_reduce import named, periods, shifted, step_idle


def _within(host_events, name: str, a: int, b: int) -> list:
    return [e for e in named(host_events, name) if a <= e[1] < b]


def _partitioned(host_events, way: dict, spans: dict, a: int, b: int):
    """Why the four spans do not split ``engine.pick`` and
    ``engine.dispatch`` between ``a`` and ``b``, one each of every one
    of them; None where they do."""
    picks = _within(host_events, spans["parts"]["pick"], a, b)
    dispatches = _within(host_events, spans["dispatched"], a, b)
    for name, outer in ((way["fetch"], picks), (way["walk"], picks),
                        (way["prepare"], dispatches),
                        (way["launch"], dispatches)):
        n = len(_within(host_events, name, a, b))
        if n != len(outer):
            return (f"{n} {name} spans against {len(outer)} of their "
                    f"parent in the whole steps")
    return None


def way_back(op_events, host_events, module_events, spans: dict,
             way: dict) -> dict:
    """``spans``: ``step_idle_ms``'s own (the marker, the steps counted,
    the clock shift's anchor); ``way``: the four spans' names
    (``fetch``, ``walk``, ``prepare``, ``launch``) and the two programs'
    (``program``, ``pick``). Returns ``{"steps", "idle_ns", "ns",
    "ms_a_step", "sum_ms_a_step", "fetch_ms_a_step", "step_idle_ms",
    "device_clock_shift_ns", "bracket"}``: ``ns`` and ``ms_a_step`` by
    the short names ``walk``, ``prepare``, ``launch``, ``wait``, which
    add up to ``idle_ns`` exactly; ``step_idle_ms`` is that table's sum
    and its ``.pick + .dispatch``, a step. Where there is nothing to
    read, only ``{"silent": why}``."""
    accepted = step_idle(op_events, host_events, spans, module_events)
    if "silent" in accepted:
        return accepted
    shift = accepted["device_clock_shift_ns"]
    _marks, a, b = periods(shifted(op_events, shift), host_events,
                           spans["step"])
    why = _partitioned(host_events, way, spans, a, b)
    if why:
        return {"silent": why}
    steps, idle = accepted["steps"], accepted["idle_ns"]
    ns = {short: sum(d for _n, _s, d in _within(host_events, way[short],
                                                a, b))
          for short in ("walk", "prepare", "launch")}
    ns["wait"] = idle - sum(ns.values())
    fetch = sum(d for _n, _s, d in _within(host_events, way["fetch"], a, b))
    ms = accepted["ms_a_step"]
    return {"steps": steps, "idle_ns": idle, "ns": ns,
            "ms_a_step": {k: v / steps / 1e6 for k, v in ns.items()},
            "sum_ms_a_step": idle / steps / 1e6,
            "fetch_ms_a_step": fetch / steps / 1e6,
            "step_idle_ms": {"sum_ms_a_step": sum(ms.values()),
                             "pick_plus_dispatch_ms_a_step":
                             ms["pick"] + ms["dispatch"]},
            "device_clock_shift_ns": shift,
            "bracket": bracket(module_events, host_events, way, a, b,
                               shift)}


def _pairs(module_events, host_events, way: dict, a: int, b: int):
    """``(launch, run, pick run, fetch)`` for each launch between ``a``
    and ``b``: the decode program's run that starts nearest the launch's
    end, inside half the least distance between two launches
    (``clock_shift``'s rule); the last pick run that starts after the
    run before it and before it; the last fetch that ended before the
    launch began. Returns the pairs and the launches left unpaired."""
    launches = named(host_events, way["launch"])
    ends = [s + d for _n, s, d in launches]
    runs = sorted(trace_reduce.matching(module_events, way["program"]),
                  key=lambda e: e[1])
    starts = [e[1] for e in runs]
    picks = sorted(trace_reduce.matching(module_events, way["pick"]),
                   key=lambda e: e[1])
    pick_starts = [e[1] for e in picks]
    fetches = sorted(named(host_events, way["fetch"]),
                     key=lambda e: e[1] + e[2])
    fetch_ends = [s + d for _n, s, d in fetches]
    if len(ends) < 2 or not runs:
        return [], sum(1 for e in launches if a <= e[1] < b)
    reach = min(y - x for x, y in zip(ends, ends[1:])) // 2
    out, unpaired = [], 0
    for launch, end in zip(launches, ends):
        if not a <= launch[1] < b:
            continue
        i = bisect.bisect_left(starts, end)
        k = min((j for j in (i - 1, i) if 0 <= j < len(runs)),
                key=lambda j: abs(end - starts[j]))
        p = bisect.bisect_left(pick_starts, starts[k]) - 1
        f = bisect.bisect_right(fetch_ends, launch[1]) - 1
        if (abs(end - starts[k]) >= reach or p < 0 or f < 0
                or (k and pick_starts[p] <= starts[k - 1])):
            unpaired += 1
            continue
        out.append((launch, runs[k], picks[p], fetches[f]))
    return out, unpaired


def bracket(module_events, host_events, way: dict, a: int, b: int,
            shift: int) -> dict:
    """Each step's ``[lo, hi]`` for the device's clock, and what they
    say together: the median and largest width, ``max lo`` against
    ``min hi`` (a steady offset exists only where the first is no
    greater), the drift of ``lo`` from the slice's first half to its
    second (medians), whether ``shift`` (the accepted one) lies inside
    ``[max lo, min hi]`` and else by how much it misses, and the most of
    ``launch`` the program can have overlapped, a step. Where a step's
    anchors contradict each other, or nothing pairs, only
    ``{"silent": why}``."""
    pairs, unpaired = _pairs(module_events, host_events, way, a, b)
    if not pairs:
        return {"silent": f"no {way['launch']} pairs with a "
                          f"{way['program']} run and a {way['pick']} run"}
    lo, hi = [], []
    for launch, run, pick, fetch in pairs:
        lo.append(launch[1] - run[1])
        hi.append(fetch[1] + fetch[2] - (pick[1] + pick[2]))
        if lo[-1] > hi[-1]:
            return {"silent": f"the step launched at {launch[1]} ns has "
                              f"lo {lo[-1]} > hi {hi[-1]} ns: the spans or "
                              f"the pairing are wrong"}
    widths = [h - x for x, h in zip(lo, hi)]
    max_lo, min_hi = max(lo), min(hi)
    half = len(lo) // 2
    steady = max_lo <= min_hi
    # with one offset, the program starts no earlier than max lo puts
    # it; without one, a step's own lo, which is its launch's start
    starts = [run[1] + (max_lo if steady else x)
              for (_l, run, _p, _f), x in zip(pairs, lo)]
    overlap = [max(0, launch[1] + launch[2] - t)
               for (launch, _r, _p, _f), t in zip(pairs, starts)]
    miss = max(max_lo - shift, shift - min_hi, 0)
    return {"steps": len(pairs), "unpaired": unpaired,
            "width_ns_median": statistics.median(widths),
            "width_ns_max": max(widths),
            "max_lo_ns": max_lo, "min_hi_ns": min_hi, "steady": steady,
            "lo_drift_ns": (statistics.median(lo[half:])
                            - statistics.median(lo[:half])
                            if half else 0),
            "accepted_shift_ns": shift,
            "accepted_shift_inside": miss == 0,
            "accepted_shift_off_by_ns": miss,
            "launch_overlap_most_ms_a_step": (
                sum(overlap) / len(pairs) / 1e6)}


def read(run: dict, params: dict):
    """What each ``way_back_ms.*`` reader does: the table once a run,
    left under ``notes["way_back"]``, and of it the part
    ``params["part"]`` names. None where the slice holds no device
    plane, no whole step, or steps without the four spans."""
    trace, notes = run["trace"], run["notes"]
    if not trace:
        return None
    if "way_back" not in notes:
        notes["way_back"] = way_back(trace["ops"], trace["host"],
                                     trace["modules"], params["spans"],
                                     params["way_back"])
    return notes["way_back"].get("ms_a_step", {}).get(params["part"])
