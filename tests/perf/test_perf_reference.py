"""The plain reference against the program at a tiny size on the CPU:
the same weights from the same seed, the same logits, the same losses —
and the arithmetic that the benchmark's shares rest on."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import flops, manifest, reference  # noqa: E402
from perf.kinds.serve import llama_config  # noqa: E402
from perf.kinds.train import worst_leaf_gap  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    cell = manifest.cell("serve-chat-steady", dry_run=True)
    return cell["config"], reference.dims_of(cell["config"])


def test_reference_imports_nothing_of_the_program():
    src = (ROOT / "perf" / "reference.py").read_text()
    assert "kubeflow_rm_tpu" not in src.split('"""', 2)[2]


def test_weights_from_the_seed_are_the_programs(tiny):
    from kubeflow_rm_tpu.models import init_params
    config, d = tiny
    cfg = llama_config(config)
    seed = 2 ** 31 + 5
    theirs = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(seed))
    ours = reference.init_weights(d, seed, jnp.float32)
    flat = {"/".join(str(k.key) for k in p): a for p, a in
            jax.tree_util.tree_flatten_with_path(theirs)[0]}
    assert set(flat) == set(reference.LEAVES)
    for name, a in flat.items():
        if name.startswith("blocks/"):
            b = jnp.stack([ours[f"{name}#{i}"] for i in range(d["L"])])
        else:
            b = ours[name]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-9)


def test_logits_are_the_programs(tiny):
    from kubeflow_rm_tpu.models import forward, init_params
    config, d = tiny
    cfg = llama_config(config)
    tokens = np.random.default_rng(0).integers(1, d["V"], (3, 24))
    params = init_params(cfg, jax.random.key(9))
    theirs = forward(params, jnp.asarray(tokens, jnp.int32), cfg)
    ours = reference.forward_logits(
        reference.init_weights(d, 9, jnp.float32), tokens, d)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=2e-5)
    gaps = reference.served_gaps(ours, jnp.argmax(theirs, -1).astype(
        jnp.int32))
    assert float(gaps.max()) < 1e-4


def test_the_control_reads_apart_from_the_reference(tiny):
    """Eight-bit operands move the logits by far more than rounding."""
    config, d = tiny
    tokens = np.random.default_rng(1).integers(1, d["V"], (2, 32))
    w = reference.init_weights(d, 3, jnp.float32)
    exact = reference.forward_logits(w, tokens, d)
    low = reference.forward_logits(w, tokens, d, quant="int8")
    err = float(jnp.abs(low - exact).max() / jnp.abs(exact).max())
    assert 1e-3 < err < 0.2


def test_learning_rate_is_optaxs():
    import optax
    optim = {"learning_rate": 3e-4, "warmup_steps": 1, "total_steps": 10000}
    sched = optax.warmup_cosine_decay_schedule(
        0.0, 3e-4, 1, 10000, end_value=3e-5)
    for count in (0, 1, 2, 500, 9999, 20000):
        assert reference.lr_at(optim, count) == pytest.approx(
            float(sched(count)), rel=1e-5, abs=1e-12)


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_base():
    ref = {"a": 1.0, "b": 0.5, "c": 1e-6}
    prog = {"a": 1.1, "b": 0.5, "c": 0.0}       # c: all but zero
    gap, where = worst_leaf_gap(prog, ref)
    assert where == "a" and gap == pytest.approx(0.1)
    gap, where = worst_leaf_gap({"a": 1.0, "b": 0.0, "c": 1e-6}, ref)
    assert where == "b" and gap == pytest.approx(1.0)   # b did not move


def test_flops_arithmetic_is_the_programs():
    from kubeflow_rm_tpu.utils import flops as theirs
    cell = manifest.cell("train-packed-4k")
    d = reference.dims_of(cell["config"])
    cfg = llama_config(cell["config"])
    assert flops.matmul_params(d) == theirs.matmul_param_count(cfg)
    assert flops.train_flops_per_token(d, 4096) == pytest.approx(
        theirs.train_flops_per_token(cfg, 4096))


def test_serve_flops_and_bytes_by_hand():
    d = {"L": 1, "D": 4, "H": 2, "KVH": 1, "hd": 2, "F": 8, "V": 10}
    P = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8 + 4 * 10
    assert flops.matmul_params(d) == P
    # prompt 3 all prefilled, 2 new: 4 tokens through the model,
    # attending 1 + 2 + 3 + 4 positions
    assert flops.serve_flops(d, 3, 2, 3) == 2 * P * 4 + 4 * 1 * 2 * 2 * 10
    assert flops.decode_step_bytes(d, 100) == 2 * (P + 2 * 1 * 1 * 2 * 100)
    f, b = flops.flash_call_cost(d, 1, 8, 36.0, backward=False)
    assert f == 4 * 1 * 2 * 2 * 36
    assert b == 2 * (2 * 8 * 2 * 2) + 2 * (2 * 8 * 1 * 2)


def test_attended_pairs_of_a_packed_row():
    from perf.kinds.train import attended_pairs
    seg = np.array([[1, 1, 1, 2, 2, 0, 0, 0],      # 3 + 2 tokens, 3 pads
                    [1, 1, 1, 1, 1, 1, 1, 1]])     # one document
    assert attended_pairs(seg) == ((6 + 3) + 36) / 2
