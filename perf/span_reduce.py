"""From the program's own spans and the device's operations to what
the host cost the chip: the device's idle time cut along the phases of
the engine's step, and the host's share of a training step. Works on
plain lists of events, as ``trace_reduce`` does, so that it can be
checked on a hand-made list; the two ``read_*`` functions at the end
are what the metrics' readers call with a run.

An event is ``(name, start_ns, duration_ns)``; an interval here is
``(start_ns, end_ns)``. Spans are taken by their exact names, which
each metric's ``.json`` gives: never by what happens to cover a gap.

The device plane's clock is not the host planes': on the v5e the two
lay 0.4 and 1.4 ms apart in two runs (my chip runs, PR 30), the device
early, which put a decode program's start before the call that
launched it and all of the idle time it ended under the phase before.
So ``clock_shift`` first moves the device's events to where the trace's
own causality puts them: a program starts inside the host span that
launches it, and the launch is the last thing that span does.

A period runs from the start of one whole marker span (``engine.step``,
``train.shard_batch``) to the start of the next, so the periods of a
slice are those of its whole markers but the last, which only closes
the one before it; a marker the slice cuts at either end is left out,
with the time it owns.
"""

import bisect

from perf import trace_reduce


def named(events, name: str) -> list:
    """The events called exactly ``name``, earliest first."""
    return sorted((e for e in events if e[0] == name), key=lambda e: e[1])


def merge(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def intersect(xs, ys) -> list:
    """Of two merged lists, the points in both."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list:
    """Of two merged lists, the points of the first not in the second."""
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > a:
                out.append((a, ys[k][0]))
            a = max(a, ys[k][1])
            k += 1
        if b > a:
            out.append((a, b))
    return out


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def _intervals(events) -> list:
    return merge((s, s + d) for _n, s, d in events)


def clock_shift(module_events, host_events, launch: dict) -> int:
    """Nanoseconds to add to the device's times. ``launch`` names a
    host ``span`` and the ``module`` (a part of the program's name) it
    launches as its last act. Each span is paired with the run of the
    module that starts nearest its end, where that is nearer than half
    the least distance between two spans (a run the slice holds with no
    span of its own pairs with none); the shift is the largest that
    still starts every paired run before its span ends: the smallest of
    span end minus run start. 0 where nothing pairs."""
    ends = sorted(s + d for _n, s, d in named(host_events, launch["span"]))
    starts = sorted(e[1] for e in trace_reduce.matching(module_events,
                                                        launch["module"]))
    if len(ends) < 2 or not starts:
        return 0
    reach = min(b - a for a, b in zip(ends, ends[1:])) // 2
    gaps = []
    for end in ends:
        i = bisect.bisect_left(starts, end)
        near = min(starts[max(i - 1, 0):i + 1], key=lambda t: abs(end - t))
        if abs(end - near) < reach:
            gaps.append(end - near)
    return min(gaps, default=0)


def shifted(events, by: int) -> list:
    return [(n, s + by, d) for n, s, d in events]


def periods(op_events, host_events, marker: str):
    """``(whole markers, a, b)``: the spans called ``marker`` that lie
    whole between the first device operation's start and the last
    one's end (the window ``device_idle_pct`` uses), and the stretch
    from the first one's start to the last one's. None where there is
    no device operation or fewer than two such spans."""
    if not op_events:
        return None
    lo = min(e[1] for e in op_events)
    hi = max(e[1] + e[2] for e in op_events)
    marks = [e for e in named(host_events, marker)
             if lo <= e[1] and e[1] + e[2] <= hi]
    if len(marks) < 2:
        return None
    return marks, marks[0][1], marks[-1][1]


def step_idle(op_events, host_events, spans: dict,
              module_events=()) -> dict:
    """The device's idle time over the engine's steps, cut along the
    host's spans, the device's events first moved by ``clock_shift``
    (``spans["launch"]``, against ``module_events``: the program runs).
    ``spans`` names them: ``step`` (the marker),
    ``dispatched`` (a step holding one of these ran the decode program:
    the steps counted), ``parts`` (short name -> the span that
    partitions a step) and ``around`` (short name -> a span around the
    step: its idle time outside the step is that part's). What lies
    under none of them, slivers between a step's parts included, is
    ``unattributed``. Returns ``{"steps", "period_ms", "idle_ns",
    "ns", "ms_a_step", "device_clock_shift_ns"}``, the third and fourth
    by short name; the parts add up to ``idle_ns`` exactly. Where
    there is nothing to read, only ``{"silent": why}``."""
    shift = clock_shift(module_events, host_events, spans["launch"])
    op_events = shifted(op_events, shift)
    found = periods(op_events, host_events, spans["step"])
    if found is None:
        return {"silent": f"fewer than two whole {spans['step']} spans "
                          f"inside the device's window"}
    marks, a, b = found
    dispatches = named(host_events, spans["dispatched"])
    steps = sum(1 for _n, s, d in marks[:-1]
                if any(s <= e[1] < s + d for e in dispatches))
    if not steps:
        return {"silent": f"no {spans['step']} of the slice holds a "
                          f"{spans['dispatched']}"}
    left = subtract([(a, b)], _intervals(op_events))
    ns = {"idle": total(left)}
    for short, name in spans["parts"].items():
        under = _intervals(named(host_events, name))
        ns[short] = total(intersect(left, under))
        left = subtract(left, under)
    sliver = total(intersect(left, _intervals(marks)))
    left = subtract(left, _intervals(marks))
    for short, name in spans["around"].items():
        under = _intervals(named(host_events, name))
        ns[short] = total(intersect(left, under))
        left = subtract(left, under)
    ns["unattributed"] = sliver + total(left)
    idle = ns.pop("idle")
    return {"steps": steps, "period_ms": (b - a) / steps / 1e6,
            "idle_ns": idle, "ns": ns,
            "ms_a_step": {k: v / steps / 1e6 for k, v in ns.items()},
            "device_clock_shift_ns": shift}


def host_per_step(op_events, host_events, spans: dict) -> dict:
    """The host's time in a step of a training loop: the durations of
    the spans ``spans["parts"]`` names, over the whole periods of
    ``spans["step"]`` in the slice. Returns ``{"steps", "ms_a_step",
    "sum_ms_a_step"}`` or ``{"silent": why}``."""
    found = periods(op_events, host_events, spans["step"])
    if found is None:
        return {"silent": f"fewer than two whole {spans['step']} spans "
                          f"inside the device's window"}
    marks, a, b = found
    steps = len(marks) - 1
    ms = {name: sum(d for _n, s, d in named(host_events, name)
                    if a <= s < b) / steps / 1e6
          for name in spans["parts"]}
    return {"steps": steps, "ms_a_step": ms,
            "sum_ms_a_step": sum(ms.values())}


def read_step_idle(run: dict, params: dict):
    """What each ``step_idle_ms.*`` reader does: the table once a run,
    left under ``notes["step_idle_ms"]`` with the sum of its parts and
    ``device_idle_pct``'s figure for the whole slice side by side, and
    of it the part ``params["part"]`` names. None where the slice
    holds no device plane or no whole step."""
    trace, notes = run["trace"], run["notes"]
    if not trace:
        return None
    if "step_idle_ms" not in notes:
        table = step_idle(trace["ops"], trace["host"], params["spans"],
                          trace["modules"])
        if "silent" not in table:
            table["sum_ms_a_step"] = table["idle_ns"] / table["steps"] / 1e6
            table["idle_pct_of_the_steps"] = (
                table["sum_ms_a_step"] / table["period_ms"] * 100.0)
            pct = trace_reduce.idle_pct(trace)
            table["device_idle_pct_of_the_slice"] = pct
            table["device_idle_ms_a_step"] = pct / 100.0 * table["period_ms"]
        notes["step_idle_ms"] = table
    return notes["step_idle_ms"].get("ms_a_step", {}).get(params["part"])


def read_host_per_step(run: dict, params: dict):
    """``train_host_ms_per_step``'s reader: the sum, with each span's
    share under ``notes``."""
    trace = run["trace"]
    if not trace:
        return None
    table = host_per_step(trace["ops"], trace["host"], params["spans"])
    run["notes"]["train_host_ms_per_step"] = table
    return table.get("sum_ms_a_step")
