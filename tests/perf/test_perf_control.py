"""The control has to come out as not correct: the reference, put in
the program's place and computed with eight-bit operands (the precision
below the bfloat16 the configurations state), fails a limit of each
cell on every seed tried, and a run with ``--control`` puts it through
the harness's own verdict: ``correct`` is false — at the rehearsal's
size and limits here, at the cell's own on the chip (PERF.md gives
those readings)."""

import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import manifest, reference  # noqa: E402
from perf import run as perf_run  # noqa: E402


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails_the_gap(seed):
    """At each position the token the lower precision puts first lies
    further below the reference's best than the limit allows, on some
    position; the reference's own first token lies at 0."""
    cell = manifest.cell("serve-chat-steady", dry_run=True)
    d = reference.dims_of(cell["config"])
    limit = cell["traffic"]["check"]["limits"]["served_token_gap"]
    w = reference.init_weights(d, seed, jnp.float32)
    tokens = np.random.default_rng(seed).integers(1, d["V"], (16, 64))
    exact = reference.forward_logits(w, tokens, d)
    low = reference.forward_logits(w, tokens, d, quant="int8")
    own = reference.served_gaps(exact, jnp.argmax(exact, -1).astype(jnp.int32))
    control = reference.served_gaps(exact,
                                    jnp.argmax(low, -1).astype(jnp.int32))
    assert float(own.max()) == 0.0
    assert float(control.max()) > 3 * limit


def _run(capsys, cell, seed, control, seconds="1"):
    rc = perf_run.main(["--workload", cell, "--seed", str(seed),
                        "--seconds", seconds, "--cpu-dry-run", "--control",
                        control])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    assert "FAILED" in out.err
    return json.loads(out.out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_serve_control_comes_out_not_correct(capsys, seed):
    """A whole run with the control in the program's place: the
    harness's own verdict reads false, while the program's reading,
    kept beside it, is inside the limit."""
    # a window long enough to serve some hundreds of tokens: on a few
    # dozen the eight-bit model can pick every token as the exact one
    line = _run(capsys, "serve-chat-steady", seed, "int8", seconds="4")
    assert line["info"]["tokens_compared"] >= 200
    assert line["correct"] is False
    gap = line["compared"]["served_token_gap"]
    assert gap["value"] > gap["limit"]
    assert line["info"]["control"] == "int8"
    assert line["info"]["program_served_token_gap"] <= gap["limit"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_train_control_comes_out_not_correct(capsys, seed):
    line = _run(capsys, "train-packed-4k", seed, "int8")
    assert line["correct"] is False
    failed = [k for k, c in line["compared"].items()
              if c["value"] > c["limit"]]
    assert "loss_gap" in failed or "first_grad_norm_gap" in failed, failed
    # the program itself is sound
    assert all(v <= line["compared"][k]["limit"]
               for k, v in line["info"]["program"].items())


def test_train_planted_fault_comes_out_not_correct(capsys):
    """Half of the batch left out, planted in the reference put in the
    program's place (the readings PERF.md gives for the chip)."""
    line = _run(capsys, "train-packed-4k", 14, "half-batch")
    assert line["correct"] is False
    failed = [k for k, c in line["compared"].items()
              if c["value"] > c["limit"]]
    assert "first_grad_norm_gap" in failed, failed
