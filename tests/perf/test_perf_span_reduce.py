"""``perf/span_reduce.py`` on hand-made event lists: the idle time of
the engine's steps cut along the program's spans, and the host's share
of a training step."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import span_reduce  # noqa: E402
from perf.harness import read_per_layer  # noqa: E402

METRICS = ROOT / "perf" / "metrics"
PARTS = ("admit", "pick", "dispatch", "scatter", "gateway", "unattributed")
SPANS = json.loads(
    (METRICS / "step_idle_ms.pick.json").read_text())["spans"]
WANT = {"admit": 2 * 10, "pick": 2 * (5 + 15), "dispatch": 2 * 20,
        "scatter": 0, "gateway": 2 * 5, "unattributed": 2 * 20}


def _step(at, dispatch=True):
    """One drain iteration of 100 ns from ``at``: the step is its first
    80, cut 10 / 30 / 20 / 20, with a sliver of 0 between the parts."""
    ev = [("gateway.drain", at, 100), ("engine.step", at, 80),
          ("engine.admit", at, 10), ("engine.pick", at + 10, 30)]
    if dispatch:
        ev += [("engine.dispatch", at + 40, 20),
               ("engine.scatter", at + 60, 20)]
    return ev + [("gateway.publish", at + 80, 15)]


def _busy(at):
    """The device's operations of the iteration from ``at``: the
    decode program from 60, where its dispatch returns, to 95, and a
    pick's from 15 to 25; idle elsewhere."""
    return [("%fusion.1 = bf16[4]{0} fusion(%p)", at + 60, 35),
            ("%fusion.2 = bf16[4]{0} fusion(%p)", at + 15, 10)]


def _slice(early=0):
    """Three whole iterations 120 ns apart (20 ns between them lie
    under no span), an earlier one the device's window cuts, and a
    long client span over everything, which must never be chosen.
    Returns the device's operations, the host's events and the decode
    program's runs; ``early`` sets the device's clock that far back."""
    host = [("perf.submit_and_wait", 0, 2000)]
    ops = [("%fusion.0 = bf16[4]{0} fusion(%p)", 950, 10)]
    runs = [("jit_paged_decode_step(7)", 940, 35)]     # its span: untraced
    for at in (880, 1000, 1120, 1240):
        host += _step(at)
        if at >= 1000:
            ops += _busy(at)
            runs += [("jit_paged_decode_step(7)", at + 60, 35),
                     ("jit__pick_row(3)", at + 15, 10)]
    back = span_reduce.shifted
    return back(ops, -early), host, back(runs, -early)


def test_the_parts_add_up_to_the_idle_time_of_the_steps():
    ops, host, runs = _slice()
    t = span_reduce.step_idle(ops, host, SPANS, runs)
    # 1000 and 1120 own a period each; 1240 only closes the second;
    # the step at 880 starts before the device's first operation
    assert t["steps"] == 2 and t["period_ms"] == 120 / 1e6
    # a period: idle 0-15, 25-60, 95-120
    assert t["idle_ns"] == 2 * (15 + 35 + 25)
    assert t["ns"] == WANT
    assert sum(t["ns"].values()) == t["idle_ns"]
    assert set(t["ms_a_step"]) == set(PARTS)
    assert t["device_clock_shift_ns"] == 0


@pytest.mark.parametrize("early", [37, 55, -25])
def test_a_device_clock_that_is_off_is_put_right_first(early):
    # 37 early starts the decode program before its dispatch span
    # begins, 55 before the pick's last program has come back: the
    # idle time it ends moves to the phases before unless the clocks
    # are brought together (both were seen on the chip, PR 30)
    ops, host, runs = _slice(early)
    t = span_reduce.step_idle(ops, host, SPANS, runs)
    assert t["device_clock_shift_ns"] == early
    assert t["ns"] == WANT and t["steps"] == 2
    if early == 37:
        assert span_reduce.step_idle(ops, host, SPANS)["ns"] != WANT


def test_a_step_without_a_decode_call_is_not_counted():
    ops, host, runs = _slice()
    host = [e for e in host
            if not (e[0] in ("engine.dispatch", "engine.scatter")
                    and 1120 <= e[1] < 1240)]
    t = span_reduce.step_idle(ops, host, SPANS, runs)
    assert t["steps"] == 1
    # its idle time stays in the sum, under the step but under no part
    assert t["ns"]["unattributed"] == 2 * 20 + 20
    assert sum(t["ns"].values()) == t["idle_ns"]


@pytest.mark.parametrize("ops, host", [
    ([], _slice()[1]),                              # no device plane
    (_slice()[0], [("perf.submit_and_wait", 0, 2000)]),   # the parent
    (_slice()[0], _step(1000)),                     # one whole step
])
def test_nothing_to_read_is_said_and_not_a_number(ops, host):
    assert set(span_reduce.step_idle(ops, host, SPANS,
                                     _slice()[2])) == {"silent"}
    assert set(span_reduce.host_per_step(
        ops, host, {"step": "train.shard_batch",
                    "parts": ["train.step"]})) == {"silent"}


def test_the_six_readers_through_the_harness():
    ops, host, runs = _slice()
    cell = {"per_layer": [{"name": f"step_idle_ms.{p}"} for p in PARTS]}
    run = {"trace": {"ops": ops, "host": host, "modules": runs,
                     "busy_s": 6e-7, "window_s": 1e-6}}
    got = read_per_layer(cell, run)
    note = run["notes"]["step_idle_ms"]
    assert sum(got.values()) == pytest.approx(note["sum_ms_a_step"],
                                              rel=1e-12)
    assert note["sum_ms_a_step"] == 75 / 1e6
    assert note["idle_pct_of_the_steps"] == pytest.approx(62.5)
    assert note["device_idle_pct_of_the_slice"] == pytest.approx(40.0)
    assert got["step_idle_ms.pick"] == 20 / 1e6
    # the CPU rehearsal has no device plane: every reader is silent
    run = {"trace": None}
    assert read_per_layer(cell, run) == {}


def test_the_hosts_share_of_a_training_step():
    host, ops = [], [("%fusion.0 = f32[4]{0} fusion(%p)", 0, 4000)]
    for at in (-100, 1000, 2000, 3000):
        host += [("train.shard_batch", at, 50), ("train.step", at + 60, 30),
                 ("perf.make_batch", at + 100, 700)]
    host += [("train.log", 2100, 20)]
    cell = {"per_layer": [{"name": "train_host_ms_per_step"}]}
    run = {"trace": {"ops": ops, "host": host, "modules": [],
                     "busy_s": 4e-6, "window_s": 4e-6}}
    got = read_per_layer(cell, run)
    # iterations 1000 and 2000 are whole; 3000 closes the second
    assert got == {"train_host_ms_per_step":
                   pytest.approx((2 * 80 + 20) / 2 / 1e6)}
    assert run["notes"]["train_host_ms_per_step"]["steps"] == 2
