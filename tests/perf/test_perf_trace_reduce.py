"""The reduction from trace events to busy time, kernel time and idle
gaps gives the known answers on a hand-made event list."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import trace_reduce as tr  # noqa: E402

MS = 1_000_000
#: device operations: busy 0-10, 10-14 (two overlapping), idle 14-20,
#: busy 20-30, idle 30-40, busy 40-50  ->  busy 34 ms of 50
OPS = [("fusion.1", 0, 10 * MS), ("flash_fwd", 10 * MS, 3 * MS),
       ("copy.2", 11 * MS, 3 * MS), ("flash_fwd", 20 * MS, 10 * MS),
       ("fusion.1", 40 * MS, 10 * MS)]
HOST = [("thread", 0, 50 * MS), ("perf.make_batch", 14 * MS, 5 * MS),
        ("engine.admit", 29 * MS, 12 * MS)]


def test_busy_is_the_union_of_intervals():
    busy, window = tr.busy_and_window(OPS)
    assert busy == pytest.approx(0.034)
    assert window == pytest.approx(0.050)
    idle_pct = 100 * (1 - busy / window)
    assert idle_pct == pytest.approx(32.0)


def test_clipped_window():
    busy, window = tr.busy_and_window(OPS, lo=5 * MS, hi=25 * MS)
    assert window == pytest.approx(0.020)
    assert busy == pytest.approx(0.014)     # 5-14 and 20-25


def test_kernel_time_by_name():
    assert sum(e[2] for e in tr.matching(OPS, "flash")) == 13 * MS
    top = tr.time_by_name(OPS, 2)
    assert top[0] == ["fusion.1", pytest.approx(0.020)]
    assert top[1] == ["flash_fwd", pytest.approx(0.013)]


def test_idle_gaps_named_by_the_tightest_host_span():
    gaps = dict(map(tuple, tr.idle_gaps(OPS, HOST)))
    assert gaps == {"engine.admit": pytest.approx(0.010),
                    "perf.make_batch": pytest.approx(0.006)}


def test_idle_gap_with_no_span():
    gaps = tr.idle_gaps(OPS, [])
    assert gaps == [["(no span)", pytest.approx(0.016)]]


def test_nothing_to_read():
    assert tr.busy_and_window([]) == (0.0, 0.0)
    assert tr.idle_gaps([], HOST) == []


# ---- the flash kernels' roofline reader on a hand-made step ----------

FWD = ("%closed_call.9 = (bf16[32,4096,128]{2,1,0}, f32[32,8,4096]{2,1,0}) "
       "custom-call(bf16[32,4096,128]{2,1,0} %q, bf16[8,4096,128]{2,1,0} %k), "
       'custom_call_target="tpu_custom_call"')
BWD = ("%checkpoint.23 = bf16[32,4096,128]{2,1,0} custom-call("
       "bf16[32,4096,128]{2,1,0} %q, f32[32,8,4096]{2,1,0} %custom-call.40), "
       'custom_call_target="tpu_custom_call"')
ALLOC = "%custom-call.40 = f32[32,8,4096]{2,1,0} custom-call()"
#: a custom call with an operand that is no kernel and takes no time
MARK = ("%custom-call.33 = bf16[4096,1024]{1,0} custom-call("
        'bf16[4096,1024]{1,0} %x), custom_call_target="MoveToDevice"')
DIMS = {"L": 2, "H": 32, "KVH": 8, "hd": 128}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _flash_run(fwd_a_step, bwd_a_step, pairs):
    """Four runs of the step program, the first and the last cut by
    the slice; each whole one holds its kernel events, 1 ms each."""
    ops, modules = [], []
    for s in range(4):
        t = s * 100 * MS
        modules.append(("jit_step(1)", t, 100 * MS))
        names = [FWD] * fwd_a_step + [BWD] * bwd_a_step
        for k, name in enumerate(names):
            ops.append((name, t + k * 2 * MS, MS))
            ops.append((MARK, t + k * 2 * MS + MS, 10))
        ops.append((ALLOC, t + 99 * MS, 10))
    return {"trace": {"ops": ops, "modules": modules}, "peaks": PEAKS,
            "dims": DIMS, "grad_accum": 4, "microbatch_rows": 1,
            "seq_len": 4096, "attended_pairs_whole_steps": pairs,
            "notes": {}}


def _flash_reader():
    import importlib.util
    import json
    base = ROOT / "perf/metrics/flash_attn_roofline"
    spec = importlib.util.spec_from_file_location(
        "flash_reader", base.with_suffix(".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read, json.loads(base.with_suffix(".json").read_text())


def test_flash_roofline_of_a_known_step():
    read, params = _flash_reader()
    causal = 4096 * 4097 / 2
    run = _flash_run(16, 16, [causal, causal / 2])
    share = read(run, params)
    # a causal forward is 4 x 32 x 128 x pairs FLOPs = 0.6975 ms at the
    # peak, a backward call 2.5 times that; 16 forward events and 8
    # backward calls of two kernels each took 32 ms a step
    one = 4 * 32 * 128 * causal / 197e12
    least = (16 * one + 8 * 2.5 * one) * 1.5      # a whole and a half row
    assert share == pytest.approx(100 * least / 0.064)
    steps = run["notes"]["flash_attn_roofline"]["steps"]
    assert [s["forward_events"] for s in steps] == [16, 16]
    assert steps[1]["pairs_share_of_causal"] == pytest.approx(0.5)
    assert steps[0]["spent_ms"] == pytest.approx(32.0)


@pytest.mark.parametrize("fwd,bwd,pairs", [(8, 16, 2), (16, 8, 2),
                                           (16, 16, 3)])
def test_flash_roofline_is_silent_on_a_miscount(fwd, bwd, pairs):
    """Another number of kernel events a step, or of whole steps, than
    the configuration gives: nothing is reported, and the note says
    why."""
    read, params = _flash_reader()
    run = _flash_run(fwd, bwd, [1e6] * pairs)
    assert read(run, params) is None
    assert "expected" in run["notes"]["flash_attn_roofline"]["silent"]
