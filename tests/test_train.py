import jax
import numpy as np
import pytest

from kubeflow_rm_tpu.models import LlamaConfig
from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
from kubeflow_rm_tpu.training import (
    TrainConfig,
    init_train_state,
    make_train_step,
)
from kubeflow_rm_tpu.training.data import pack_documents, synthetic_batches
from kubeflow_rm_tpu.training.train import shard_batch
from kubeflow_rm_tpu.ops.losses import IGNORE_INDEX


from kubeflow_rm_tpu.training.optim import OptimConfig


@pytest.fixture(scope="module")
def tiny_cfg():
    return TrainConfig(
        model=LlamaConfig.tiny(),
        optim=OptimConfig(learning_rate=1e-2, warmup_steps=2, total_steps=200),
    )


def test_train_step_runs_and_loss_decreases(tiny_cfg, devices8):
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)
    state = init_train_state(tiny_cfg, jax.random.key(0))
    step = make_train_step(tiny_cfg, mesh, state)

    data = synthetic_batches(8, 32, tiny_cfg.model.vocab_size, seed=0)
    fixed = next(data)  # overfit one batch: loss must drop
    losses = []
    for _ in range(10):
        state, metrics = step(state, shard_batch(fixed, mesh))
        losses.append(float(metrics["loss"]))
    assert int(state.step) == 10
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.9, losses


def test_train_step_sp_mesh(tiny_cfg, devices8):
    # sequence-parallel layout: batch sharded over sp along T as well
    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, sp=2, tp=2), devices8)
    state = init_train_state(tiny_cfg, jax.random.key(0))
    step = make_train_step(tiny_cfg, mesh, state)
    batch = next(synthetic_batches(4, 32, tiny_cfg.model.vocab_size))
    state, metrics = step(state, shard_batch(batch, mesh))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("backend", ["ring", "ulysses"])
def test_sp_attention_backends_match_dense(tiny_cfg, devices8, backend):
    """cfg.attention_backend swaps the dense (GSPMD all-gather)
    attention for the explicit ring / all-to-all schedule inside the
    SAME train step — loss and grads must be unchanged."""
    from dataclasses import replace

    batch = next(synthetic_batches(4, 32, tiny_cfg.model.vocab_size))

    def run(cfg):
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, sp=4, tp=2), devices8)
        state = init_train_state(cfg, jax.random.key(0))
        step = make_train_step(cfg, mesh, state)
        _, m = step(state, shard_batch(batch, mesh))
        return float(m["loss"]), float(m["grad_norm"])

    ref_loss, ref_gnorm = run(tiny_cfg)
    cfg = replace(tiny_cfg,
                  model=replace(tiny_cfg.model,
                                attention_backend=backend))
    loss, gnorm = run(cfg)
    assert loss == pytest.approx(ref_loss, rel=1e-5)
    assert gnorm == pytest.approx(ref_gnorm, rel=1e-4)


def test_train_determinism(tiny_cfg, devices8):
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)
    batch = next(synthetic_batches(8, 16, tiny_cfg.model.vocab_size))

    def run():
        state = init_train_state(tiny_cfg, jax.random.key(0))
        step = make_train_step(tiny_cfg, mesh, state)
        for _ in range(3):
            state, m = step(state, shard_batch(batch, mesh))
        return float(m["loss"])

    assert run() == pytest.approx(run(), abs=1e-6)


def test_grad_accum_matches_full_batch(tiny_cfg, devices8):
    """K sequential microbatches + one optimizer update must equal the
    full-batch step (same loss, same resulting params) up to
    accumulation-order rounding — the contract that makes grad_accum a
    pure memory/HBM knob, not a hyperparameter change."""
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)
    batch = next(synthetic_batches(8, 32, tiny_cfg.model.vocab_size))

    def run(k):
        state = init_train_state(tiny_cfg, jax.random.key(0))
        step = make_train_step(tiny_cfg, mesh, state, grad_accum=k)
        state, m = step(state, shard_batch(batch, mesh))
        return float(m["loss"]), float(m["grad_norm"]), state.params

    loss1, gnorm1, params1 = run(1)
    loss4, gnorm4, params4 = run(4)
    assert loss4 == pytest.approx(loss1, rel=1e-5)
    assert gnorm4 == pytest.approx(gnorm1, rel=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(params1),
                    jax.tree_util.tree_leaves(params4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_grad_accum_rejects_indivisible_batch(tiny_cfg, devices8):
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)
    state = init_train_state(tiny_cfg, jax.random.key(0))
    step = make_train_step(tiny_cfg, mesh, state, grad_accum=3)
    batch = next(synthetic_batches(8, 32, tiny_cfg.model.vocab_size))
    with pytest.raises(ValueError, match="grad_accum"):
        step(state, shard_batch(batch, mesh))


def test_pack_documents():
    docs = [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10]]
    out = pack_documents(docs, seq_len=4)
    assert out["tokens"].shape[1] == 4
    assert out["positions"].shape == out["tokens"].shape
    # first row is doc1[:4], labels shifted by one
    assert list(out["tokens"][0]) == [1, 2, 3, 4]
    assert list(out["labels"][0]) == [2, 3, 4, 5]
    assert list(out["positions"][0]) == [0, 1, 2, 3]
    # ignore-index appears at doc boundaries / padding
    assert (out["labels"] == IGNORE_INDEX).sum() >= 1


def test_factored_optimizer_trains_and_state_is_small(tiny_cfg, devices8):
    """adafactor option: loss falls, and the optimizer state holds no
    params-sized moment buffers (the ~3B-on-one-v5e memory shape)."""
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)
    cfg = TrainConfig(
        model=tiny_cfg.model,
        # adafactor steps are parameter-RELATIVE (x param RMS), so a
        # 30-step test needs a large relative rate where adam's
        # absolute 1e-2 sufficed
        optim=OptimConfig(learning_rate=0.3, warmup_steps=2,
                          total_steps=200, factored=True,
                          factored_min_dim=8),
    )
    state = init_train_state(cfg, jax.random.key(0))
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(state.params))
    n_opt = sum(x.size for x in
                jax.tree_util.tree_leaves(state.opt_state)
                if hasattr(x, "size"))
    # factored stats are O(rows+cols): far below one param-sized buffer
    assert n_opt < 0.2 * n_params, (n_opt, n_params)

    step = make_train_step(cfg, mesh, state)
    fixed = next(synthetic_batches(8, 64, cfg.model.vocab_size, seed=0))
    losses = []
    for _ in range(30):                # overfit one batch: loss must drop
        state, metrics = step(state, shard_batch(fixed, mesh))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[::6]


def test_factored_optimizer_with_grad_accum(tiny_cfg, devices8):
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)
    cfg = TrainConfig(
        model=tiny_cfg.model,
        optim=OptimConfig(learning_rate=1e-2, warmup_steps=2,
                          total_steps=200, factored=True),
    )
    state = init_train_state(cfg, jax.random.key(0))
    step = make_train_step(cfg, mesh, state, grad_accum=4)
    batches = synthetic_batches(8, 64, cfg.model.vocab_size, seed=0)
    for _, batch in zip(range(3), batches):
        state, metrics = step(state, shard_batch(batch, mesh))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("path", ["flash_packed", "flash_unpacked",
                                  "xla_packed"])
def test_flash_tiles_live_share_is_logged_only_on_the_packed_flash_path(
        path, devices8):
    """``fit()`` logs the share of causal tiles the step's segment ids
    left the flash kernels to visit — when segment ids reach the flash
    kernels (forced here, as the chip's "auto" cannot be on a CPU), and
    at no other time; it is the count ``flash_tile_counts`` makes of
    the batch, averaged over the microbatches."""
    from functools import partial
    from unittest import mock

    from kubeflow_rm_tpu.models import llama
    from kubeflow_rm_tpu.ops import dot_product_attention
    from kubeflow_rm_tpu.ops import flash_attention as fa
    from kubeflow_rm_tpu.training.loop import LoopConfig, fit

    cfg = TrainConfig(model=LlamaConfig.tiny(max_seq_len=256),
                      optim=OptimConfig(learning_rate=1e-3))
    mesh = make_mesh(MeshConfig(fsdp=1), devices8[:1])
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, 200, size=n).tolist()
            for n in (90, 30, 150, 20, 70, 200, 60, 45, 110)]
    batch = {k: v[:2] for k, v in pack_documents(docs, seq_len=256).items()}
    if path == "flash_unpacked":
        batch = {k: batch[k] for k in ("tokens", "labels")}
    impl = "xla" if path == "xla_packed" else "flash"
    # rows this short would be one 256 tile each: 64 leaves something
    # to skip
    with mock.patch.object(llama, "dot_product_attention",
                           partial(dot_product_attention, impl=impl)), \
            mock.patch.object(fa, "PACKED_BLOCK_Q", 64), \
            mock.patch.object(fa, "PACKED_BLOCK_K", 64):
        _, history = fit(cfg, mesh, [batch, batch],
                         LoopConfig(total_steps=2, log_every=1,
                                    grad_accum=2))
        shares = [rec.flash_tiles_live_share for rec in history]
        if path != "flash_packed":
            assert shares == [None, None]
            return
        # a row a microbatch
        counts = [fa.flash_tile_counts(batch["segments"][r:r + 1])
                  for r in range(2)]
    assert all(int(causal) == 10 and int(live) < 10
               for live, causal in counts)
    want = np.mean([int(live) / int(causal) for live, causal in counts])
    np.testing.assert_allclose(shares, [want, want], rtol=1e-6)
