"""``perf/way_back.py`` on hand-made event lists: the device's idle time
over the engine's whole steps cut into the host's walk, preparation
and launch and the wait that is left, none of them moved by the
device's clock, and the bracket each step's anchors put on that
clock."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import check_manifest, manifest, span_reduce, way_back  # noqa: E402
from perf.harness import read_per_layer  # noqa: E402

METRICS = ROOT / "perf" / "metrics"
PARTS = ("walk", "prepare", "launch", "wait")
PARAMS = json.loads((METRICS / "way_back_ms.walk.json").read_text())
SPANS, WAY = PARAMS["spans"], PARAMS["way_back"]
SERVING = ["serve-chat-steady", "serve-reason-steady", "serve-reason-long"]
# a period is idle 0-15, 25-60 and 95-120: 75 ns, of which the walk
# takes 12, the preparation 6 and the launch 14
WANT = {"walk": 12, "prepare": 6, "launch": 14, "wait": 75 - 32}


def _step(at, new_spans=True):
    """One drain iteration of 100 ns from ``at``: the step is its first
    80, cut 10 / 30 / 20 / 20; the pick's 30 are a fetch of 18 and a
    walk of 12, the dispatch's 20 a preparation of 6 and a launch of
    14."""
    ev = [("gateway.drain", at, 100), ("engine.step", at, 80),
          ("engine.admit", at, 10), ("engine.pick", at + 10, 30),
          ("engine.dispatch", at + 40, 20), ("engine.scatter", at + 60, 20),
          ("gateway.publish", at + 80, 15)]
    if new_spans:
        ev += [("engine.pick.fetch", at + 10, 18),
               ("engine.pick.walk", at + 28, 12),
               ("engine.dispatch.prepare", at + 40, 6),
               ("engine.dispatch.launch", at + 46, 14)]
    return ev


def _slice(n=3, early=lambda k: 0, late_run=None, new_spans=True):
    """``n`` iterations 120 ns apart from 1000 (the last only closes
    the one before it), an earlier one the device's window cuts, and an
    operation well after them. The pick program runs 15-25 of an
    iteration, the fetch returns at 28; the decode program runs 60-95,
    from where its launch returns (``late_run``: iteration -> a later
    start). The device's clock of iteration ``k`` lies ``early(k)`` back.
    Returns the device's operations, the host's events and the runs."""
    host = [("perf.submit_and_wait", 0, 5000)]
    ops = span_reduce.shifted(
        [("%fusion.0 = bf16[4]{0} fusion(%p)", 950, 10)], -early(-1))
    runs = span_reduce.shifted(                         # its span: untraced
        [("jit_paged_decode_step(7)", 940, 35)], -early(-1))
    last = 1000 + 120 * (n - 1)
    for k, at in enumerate([880] + list(range(1000, last + 1, 120))):
        host += _step(at, new_spans)
        if at < 1000:
            continue
        start = at + (late_run or {}).get(k - 1, 60)
        mine = [("%fusion.1 = bf16[4]{0} fusion(%p)", start, 35),
                ("%fusion.2 = bf16[4]{0} fusion(%p)", at + 15, 10)]
        ran = [("jit_paged_decode_step(7)", start, 35),
               ("jit__pick_row(3)", at + 15, 10)]
        ops += span_reduce.shifted(mine, -early(k - 1))
        runs += span_reduce.shifted(ran, -early(k - 1))
    ops.append(("%fusion.9 = bf16[4]{0} fusion(%p)", last + 300, 10))
    return ops, host, runs


def _table(ops, host, runs):
    return way_back.way_back(ops, host, runs, SPANS, WAY)


def test_the_four_parts_add_up_to_the_idle_time_of_the_steps():
    t = _table(*_slice())
    assert t["steps"] == 2 and t["idle_ns"] == 2 * 75
    assert t["ns"] == {k: 2 * v for k, v in WANT.items()}
    assert sum(t["ns"].values()) == t["idle_ns"]
    assert t["ms_a_step"] == {k: v / 1e6 for k, v in WANT.items()}
    assert t["sum_ms_a_step"] == 75 / 1e6
    assert t["fetch_ms_a_step"] == 18 / 1e6
    # the same steps and the same total as step_idle_ms's
    ops, host, runs = _slice()
    accepted = span_reduce.step_idle(ops, host, SPANS, runs)
    assert (accepted["steps"], accepted["idle_ns"]) == (2, t["idle_ns"])


@pytest.mark.parametrize("early", [37, 55, -25])
def test_a_device_clock_that_is_off_moves_none_of_the_four(early):
    t = _table(*_slice(early=lambda k: early))
    assert t["ns"] == {k: 2 * v for k, v in WANT.items()}
    b = t["bracket"]
    # each step: lo = launch begin - run start = early - 14,
    # hi = fetch end - pick run end = early + 3
    assert (b["max_lo_ns"], b["min_hi_ns"]) == (early - 14, early + 3)
    assert b["max_lo_ns"] <= early <= b["min_hi_ns"] and b["steady"]
    assert b["width_ns_median"] == b["width_ns_max"] == 17
    assert b["lo_drift_ns"] == 0 and b["steps"] == 2
    assert b["accepted_shift_ns"] == early and b["accepted_shift_inside"]
    # the program can have overlapped the whole launch: the bracket
    # sets its earliest start at the launch's begin
    assert b["launch_overlap_most_ms_a_step"] == 14 / 1e6


def test_a_program_started_later_bounds_the_launch_it_overlapped():
    # the second step's program starts 5 ns after its launch returned:
    # under one offset the first step's lo holds it too
    t = _table(*_slice(late_run={1: 65}))
    b = t["bracket"]
    assert (b["max_lo_ns"], b["min_hi_ns"]) == (-14, 3)
    assert b["launch_overlap_most_ms_a_step"] == (14 + 9) / 2 / 1e6
    # the accepted shift (-5: every program before its dispatch ends)
    # lies inside the bracket, and the four did not move
    assert b["accepted_shift_ns"] == -5 and b["accepted_shift_inside"]
    assert t["ms_a_step"]["walk"] == 12 / 1e6
    assert t["ms_a_step"]["launch"] == 14 / 1e6


def test_an_offset_that_steps_mid_slice_shows_as_drift():
    # from the fourth iteration on the device's clock lies 40 ns further
    # back: no one offset fits both halves
    t = _table(*_slice(n=6, early=lambda k: 40 if k >= 3 else 0))
    b = t["bracket"]
    assert b["steps"] == 5 and b["lo_drift_ns"] == 40
    assert (b["max_lo_ns"], b["min_hi_ns"]) == (26, 3) and not b["steady"]
    # the accepted shift is the smallest gap, 0: the second half's
    # brackets miss it by 26 ns, so its split is off by that at least
    assert b["accepted_shift_ns"] == 0
    assert not b["accepted_shift_inside"]
    assert b["accepted_shift_off_by_ns"] == 26
    assert {k: t["ns"][k] for k in ("walk", "prepare", "launch")} == {
        k: 5 * WANT[k] for k in ("walk", "prepare", "launch")}
    assert sum(t["ns"].values()) == t["idle_ns"]


def test_contradictory_anchors_silence_the_bracket_not_the_metrics():
    ops, host, runs = _slice()
    # a pick run that ends after its fetch returned: hi -22 < lo -14
    runs = [("jit__pick_row(3)", 1160, 10) if r == ("jit__pick_row(3)",
                                                    1135, 10) else r
            for r in runs]
    t = _table(ops, host, runs)
    assert set(t["bracket"]) == {"silent"}
    assert "lo -14 > hi -22" in t["bracket"]["silent"]
    assert t["ns"] == {k: 2 * v for k, v in WANT.items()}


@pytest.mark.parametrize("ops, host", [
    ([], _slice()[1]),                              # no device plane
    (_slice()[0], _step(1000)),                     # one whole step
    (_slice()[0], _slice(new_spans=False)[1]),      # a program without them
], ids=["no-device", "one-step", "no-spans"])
def test_nothing_to_read_is_said_and_not_a_number(ops, host):
    assert set(_table(ops, host, _slice()[2])) == {"silent"}


def test_the_four_readers_through_the_harness():
    ops, host, runs = _slice()
    cell = {"per_layer": [{"name": f"way_back_ms.{p}"} for p in PARTS]}
    run = {"trace": {"ops": ops, "host": host, "modules": runs,
                     "busy_s": 6e-7, "window_s": 1e-6}}
    got = read_per_layer(cell, run)
    assert got == {f"way_back_ms.{k}": v / 1e6 for k, v in WANT.items()}
    note = run["notes"]["way_back"]
    assert sum(got.values()) == pytest.approx(note["sum_ms_a_step"],
                                              rel=1e-12)
    # beside it, the six step_idle_ms.* of the same slice: the same
    # total, of which the accepted .pick + .dispatch take 40 ns a step
    assert note["step_idle_ms"] == {
        "sum_ms_a_step": pytest.approx(75 / 1e6, rel=1e-12),
        "pick_plus_dispatch_ms_a_step": pytest.approx(40 / 1e6, rel=1e-12)}
    # the CPU rehearsal has no device plane, the parent's program no
    # such spans: every reader is silent and none raises
    assert read_per_layer(cell, {"trace": None}) == {}
    parent = {"trace": {**run["trace"],
                        "host": _slice(new_spans=False)[1]}}
    assert read_per_layer(cell, parent) == {}
    assert parent["notes"]["way_back"]["silent"].startswith(
        "0 engine.pick.fetch spans")


def test_the_four_entries_keep_to_the_contract():
    assert check_manifest.check() == []
    per = {m["name"]: m for m in manifest.load()["per_layer"]}
    for p in PARTS:
        m = per[f"way_back_ms.{p}"]
        assert (m["layer"], m["moves"], m["source"], m["unit"]) == (
            "engine", "req_latency_p95_ms", "program_span", "ms")
        assert m["workloads"] == SERVING
        params = json.loads((METRICS / f"way_back_ms.{p}.json").read_text())
        assert params["part"] == p
        assert params["spans"] == json.loads(
            (METRICS / "step_idle_ms.pick.json").read_text())["spans"]
