"""The harness end to end at a tiny size on the CPU (``--cpu-dry-run``):
one serve and one train cell run whole and come out correct, and with
the timed path broken underneath ``correct`` comes out false — once for
each fault a one-chip cell can have. The chip check is the only thing
skipped: these drive perf/run.py's ``main`` in-process."""

import importlib
import json
import sys
from pathlib import Path
from unittest import mock

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import run as perf_run  # noqa: E402


def _run(capsys, cell, *extra, seed=2 ** 31 + 77):
    rc = perf_run.main(["--workload", cell, "--seed", str(seed),
                        "--seconds", "2", "--cpu-dry-run", *extra])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"     # the compared numbers come last
    assert "compared " in out.err.strip().splitlines()[-1]
    return line


def _no_time_rate_or_share(line):
    assert line["cpu_dry_run"] is True
    assert line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values())
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_serve_cell_rehearsal(capsys):
    line = _run(capsys, "serve-chat-steady", "--trace", "0")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"req_latency_p95_ms", "serve_tok_s",
                                    "setup_s"}
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert line["info"]["tokens_compared"] > 0
    assert line["compared"]["malformed_timelines"] == {"value": 0,
                                                       "limit": 0}
    # the stamps' readings ride in info: counts here, never a time
    info = line["info"]
    assert info["ttft_ms"] == {"p50": None, "p95": None,
                               "n": line["attempted"]}
    assert info["itl_ms"]["n"] > 0 and info["itl_ms"]["p95"] is None
    assert 0 <= info["completed_in_window"] <= line["attempted"]
    assert info["tokens_stamped_in_window"] >= info[
        "tokens_completed_in_window"] > 0
    _no_time_rate_or_share(line)


def test_serve_cell_rehearsal_traced(capsys):
    line = _run(capsys, "serve-chat-steady", "--trace", "1")
    assert line["correct"] is True
    # counters read on the CPU too; what needs a device trace or a
    # peak finds nothing to read and is left out, never reported as 0
    assert {"batch_occupancy", "decode_step_ms",
            "gateway_queue_ms_p95"} <= set(line["metrics"])
    assert not {"paged_decode_roofline", "serve_mfu_pct",
                "device_idle_pct.serve"} & set(line["metrics"])
    _no_time_rate_or_share(line)


def test_train_cell_rehearsal(capsys):
    line = _run(capsys, "train-packed-4k", "--trace", "0")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert set(line["compared"]) == {"loss_gap", "first_grad_norm_gap",
                                     "param_change_gap",
                                     "compiles_in_window"}
    # every step sent is waited for and counted
    assert line["info"]["steps_in_window"] == line["attempted"]
    _no_time_rate_or_share(line)


def test_serve_fault_token_altered_where_it_is_produced(capsys):
    generate = importlib.import_module("kubeflow_rm_tpu.models.generate")
    real = generate._pick_row
    calls = [0]

    def wrong_now_and_then(last, key, **kw):
        calls[0] += 1
        tok = real(last, key, **kw)
        return (tok + 1) % last.shape[-1] if calls[0] % 7 == 0 else tok

    with mock.patch.object(generate, "_pick_row", wrong_now_and_then):
        line = _run(capsys, "serve-chat-steady", "--trace", "0")
    assert line["correct"] is False
    gap = line["compared"]["served_token_gap"]
    assert gap["value"] > gap["limit"]


def _stamp_missing(tl):
    tl["t_tokens"] = tl["t_tokens"][:-1]


def _stamps_out_of_order(tl):
    tl["t_tokens"] = tl["t_tokens"][::-1]


def _stamps_after_the_return(tl):
    # a stamp moved to where the client cannot have had the token yet
    tl["t_tokens"] = [t + 3600.0 for t in tl["t_tokens"]]


@pytest.mark.parametrize("spoil", [_stamp_missing, _stamps_out_of_order,
                                   _stamps_after_the_return])
def test_serve_fault_a_returned_answer_with_bad_stamps_is_malformed(
        capsys, spoil):
    """Every token came back and the served tokens are right, but one
    answer in five cannot say when its tokens were made, or says a
    time outside the client's own call: the run is malformed, not a
    smaller ``serve_tok_s``."""
    generate = importlib.import_module("kubeflow_rm_tpu.models.generate")
    real = generate.EngineRequest.timeline

    def timeline(self):
        tl = real(self)
        if self.rid % 5 == 0 and len(tl["t_tokens"]) > 1:
            spoil(tl)
        return tl

    with mock.patch.object(generate.EngineRequest, "timeline", timeline):
        line = _run(capsys, "serve-chat-steady", "--trace", "0")
    assert line["correct"] is False and line["failed"] == 0
    bad = line["compared"]["malformed_timelines"]
    assert bad["value"] > 0 and bad["limit"] == 0
    gap = line["compared"]["served_token_gap"]
    assert gap["value"] <= gap["limit"]


def test_train_fault_state_returned_unchanged(capsys):
    from kubeflow_rm_tpu.training import train
    with mock.patch.object(train.optax, "apply_updates",
                           lambda params, updates: params):
        line = _run(capsys, "train-packed-4k", "--trace", "0")
    assert line["correct"] is False
    change = line["compared"]["param_change_gap"]
    assert change["value"] == pytest.approx(1.0)    # nothing moved
    assert change["value"] > change["limit"]


def test_train_fault_half_the_batch_left_out(capsys):
    """The step sees its first two rows twice: the mean is taken over
    half of the batch."""
    import numpy as np

    from kubeflow_rm_tpu.training import loop
    real = loop.shard_batch

    def half(batch, mesh):
        n = next(iter(batch.values())).shape[0] // 2
        return real({k: np.concatenate([v[:n], v[:n]])
                     for k, v in batch.items()}, mesh)

    with mock.patch.object(loop, "shard_batch", half):
        line = _run(capsys, "train-packed-4k", "--trace", "0")
    assert line["correct"] is False
    failed = [k for k, c in line["compared"].items()
              if c["value"] > c["limit"]]
    assert "first_grad_norm_gap" in failed


def test_without_an_accelerator_there_is_no_result(capsys):
    """Here jax is held to the CPU: the measuring path must refuse."""
    rc = perf_run.main(["--workload", "train-packed-4k", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "needs 1 tpu" in out.err
