"""From a profiler trace to numbers: device busy time, the time of
named operations, and the longest idle gaps with what the host was
doing in them. Works on plain lists of events, so that it can be
checked on a hand-made list; ``load`` turns the ``.xplane.pb`` that
``jax.profiler`` writes into such lists.

An event is ``(name, start_ns, duration_ns)``.
"""

import glob
import os
import re


def load(logdir: str) -> dict:
    """``{"device": {plane name: {line name: [event, ...]}},
    "host": [event, ...]}`` from the newest trace under ``logdir``.
    Device planes are those named ``/device:TPU:<n>``; host events are
    those of every line of the host planes (the harness's own
    ``TraceAnnotation`` spans are among them)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(files[-1])
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            out["device"][plane.name] = {
                line.name: [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, int(e.start_ns),
                                 int(e.duration_ns)) for e in line.events]
    return out


def union_ns(events) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def clip(events, lo: int, hi: int) -> list:
    """The parts of the events inside [lo, hi)."""
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_and_window(op_events, lo: int | None = None,
                    hi: int | None = None) -> tuple[float, float]:
    """(busy seconds, window seconds): the union of the operations'
    intervals, and the span from ``lo`` to ``hi`` (by default from the
    first operation's start to the last one's end)."""
    if not op_events:
        return 0.0, 0.0
    if lo is None:
        lo = min(e[1] for e in op_events)
    if hi is None:
        hi = max(e[1] + e[2] for e in op_events)
    return union_ns(clip(op_events, lo, hi)) / 1e9, (hi - lo) / 1e9


def short_name(name: str) -> str:
    """An XLA operation's event carries its whole HLO line:
    ``%fusion.4 = bf16[16,14336]{...} fusion(...)`` becomes
    ``fusion.4 fusion bf16[16,14336]``. Other names pass."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:96]
    shape = rhs.lstrip("(").split("{")[0].split(" ")[0]
    m = re.search(r"[\s)]([a-z][a-z\-]*)\(", rhs)
    return f"{lhs.lstrip('%')} {m.group(1) if m else '?'} {shape}"[:96]


def leaves_only(op_events) -> list:
    """Drop the events of loops and calls, whose intervals only
    contain other operations' (``while``, ``conditional``, ``call``)."""
    wrappers = (" while(", " conditional(", " call(")
    return [e for e in op_events if not any(w in e[0] for w in wrappers)]


def idle_pct(trace: dict | None) -> float | None:
    """1 - busy over the traced slice, in per cent; None where no
    device operation was traced."""
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def time_by_name(events, top: int | None = None) -> list:
    """[[name, seconds], ...], most time first; names shortened."""
    acc = {}
    for name, _start, dur in events:
        acc[name] = acc.get(name, 0) + dur
    short = {}
    for name, dur in acc.items():       # a name's events share its text
        key = short_name(name)
        short[key] = short.get(key, 0) + dur
    acc = short
    rows = sorted(acc.items(), key=lambda kv: -kv[1])
    return [[n, t / 1e9] for n, t in rows[:top]]


def matching(events, needle: str) -> list:
    return [e for e in events if needle in e[0]]


def idle_gaps(op_events, host_events, top: int = 10,
              longest: int = 200) -> list:
    """The ``longest`` gaps between device operations, each named by
    the shortest host span that covers at least half of it (``"(no
    span)"`` where none does): [[host span, seconds], ...], the time
    of equal names added up, most first."""
    import numpy as np
    ops = sorted(op_events, key=lambda e: e[1])
    gaps, end = [], None
    for _name, start, dur in ops:
        if end is not None and start > end:
            gaps.append((start - end, end, start))
        end = start + dur if end is None else max(end, start + dur)
    gaps = sorted(gaps, reverse=True)[:longest]
    h_start = np.array([e[1] for e in host_events], np.int64)
    h_dur = np.array([e[2] for e in host_events], np.int64)
    acc = {}
    for length, a, b in gaps:
        best = "(no span)"
        if len(h_start):
            cover = (np.minimum(b, h_start + h_dur)
                     - np.maximum(a, h_start))
            ok = np.flatnonzero(2 * cover >= length)
            if len(ok):
                best = host_events[ok[np.argmin(h_dur[ok])]][0]
        acc[best] = acc.get(best, 0) + length
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / 1e9] for n, t in rows]
