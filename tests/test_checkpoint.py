"""Checkpoint/resume + fit() loop.

Mirrors the reference's platform checkpoint story (PVC workspace
survives stop/start — SURVEY.md §5) at the model level: a training run
killed mid-way and resumed from its checkpoint directory must land on
the same step with the same params.
"""

import jax
import numpy as np
import pytest

from kubeflow_rm_tpu.models import LlamaConfig
from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
from kubeflow_rm_tpu.training import (
    Checkpointer, LoopConfig, TrainConfig, fit, init_train_state,
)
from kubeflow_rm_tpu.training.data import synthetic_batches


@pytest.fixture
def mesh(devices8):
    return make_mesh(MeshConfig(dp=2, fsdp=2, sp=1, tp=2), devices8)


def _cfg():
    return TrainConfig(model=LlamaConfig.tiny())


def test_checkpoint_roundtrip(tmp_path, mesh):
    cfg = _cfg()
    state = init_train_state(cfg, jax.random.key(0))
    with Checkpointer(tmp_path / "ckpt") as ck:
        assert ck.restore(cfg, mesh) is None  # empty dir
        ck.save(state, force=True)
        ck.wait()
        assert ck.latest_step() == 0
        restored = ck.restore(cfg, mesh)
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored leaves carry the mesh shardings (scales on multi-host)
    leaf = jax.tree.leaves(restored.params)[0]
    assert leaf.sharding.mesh.shape == mesh.shape


def test_fit_logs_and_checkpoints(tmp_path, mesh):
    cfg = _cfg()
    data = synthetic_batches(batch_size=8, seq_len=32,
                             vocab_size=cfg.model.vocab_size)
    state, history = fit(
        cfg, mesh, data,
        LoopConfig(total_steps=6, log_every=2, checkpoint_every=3,
                   checkpoint_dir=str(tmp_path / "ckpt")),
    )
    assert int(state.step) == 6
    assert [h.step for h in history] == [2, 4, 6]
    assert all(np.isfinite(h.loss) for h in history)
    assert all(h.tokens_per_sec > 0 for h in history)
    # CPU mesh: no chip peak -> MFU is absent, not zero
    assert all(h.mfu_pct is None for h in history)


def test_fit_resumes_from_checkpoint(tmp_path, mesh):
    cfg = _cfg()

    def data():
        return synthetic_batches(batch_size=8, seq_len=32,
                                 vocab_size=cfg.model.vocab_size)

    ckpt_dir = str(tmp_path / "ckpt")
    full, _ = fit(cfg, mesh, data(),
                  LoopConfig(total_steps=6, log_every=6, seed=7))

    fit(cfg, mesh, data(),
        LoopConfig(total_steps=3, log_every=3, checkpoint_dir=ckpt_dir,
                   seed=7))
    resumed, history = fit(
        cfg, mesh, data(),
        LoopConfig(total_steps=6, log_every=3, checkpoint_dir=ckpt_dir,
                   seed=7))
    assert int(resumed.step) == 6
    assert [h.step for h in history] == [6]  # only steps 4-6 ran

    # exact resume: fit() fast-forwards the (deterministic) data stream
    # past the 3 consumed batches, so the resumed run sees batches 3..5
    # — identical to the uninterrupted run, params and all (ADVICE r2:
    # previously the resumed run replayed batches from the start)
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(resumed.params)[0], np.float32),
        np.asarray(jax.tree.leaves(full.params)[0], np.float32),
        rtol=2e-5, atol=2e-5)


def test_fit_rejects_nonpositive_log_every(mesh):
    cfg = _cfg()
    data = synthetic_batches(batch_size=8, seq_len=32,
                             vocab_size=cfg.model.vocab_size)
    with pytest.raises(ValueError, match="log_every"):
        fit(cfg, mesh, data, LoopConfig(total_steps=2, log_every=0))


# -- host-offload optimizer state (r18) -------------------------------------

def _offload_cfg():
    from kubeflow_rm_tpu.training.optim import OptimConfig
    return TrainConfig(model=LlamaConfig.tiny(),
                       optim=OptimConfig(factored=True, offload="optimizer"))


def test_checkpoint_roundtrip_offload_opt_state(tmp_path, mesh):
    """Host-resident optimizer state survives an orbax roundtrip and
    restores back onto the HOST device, not the mesh — a resumed
    offload run must never stage adafactor stats through HBM."""
    from kubeflow_rm_tpu.training.optim import host_device
    cfg = _offload_cfg()
    state = init_train_state(cfg, jax.random.key(0))
    with Checkpointer(tmp_path / "ckpt") as ck:
        ck.save(state, force=True)
        ck.wait()
        restored = ck.restore(cfg, mesh)
    for a, b in zip(jax.tree.leaves(state.opt_state),
                    jax.tree.leaves(restored.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    host = host_device()
    for leaf in jax.tree.leaves(restored.opt_state):
        if hasattr(leaf, "devices"):
            assert leaf.devices() == {host}
    # params still restore onto the mesh as usual
    leaf = jax.tree.leaves(restored.params)[0]
    assert leaf.sharding.mesh.shape == mesh.shape


def test_fit_resumes_exactly_with_offload(tmp_path, mesh):
    """Kill-and-resume with the streamed offload step lands on the
    same step with bit-identical params AND optimizer state as the
    uninterrupted run — resume replays the same deterministic stream
    through the same host-side update arithmetic."""
    cfg = _offload_cfg()

    def data():
        return synthetic_batches(batch_size=8, seq_len=32,
                                 vocab_size=cfg.model.vocab_size)

    loop_kw = dict(log_every=3, seed=7, offload="optimizer")
    full, _ = fit(cfg, mesh, data(),
                  LoopConfig(total_steps=6, **loop_kw))

    ckpt_dir = str(tmp_path / "ckpt")
    fit(cfg, mesh, data(),
        LoopConfig(total_steps=3, checkpoint_dir=ckpt_dir, **loop_kw))
    resumed, history = fit(
        cfg, mesh, data(),
        LoopConfig(total_steps=6, checkpoint_dir=ckpt_dir, **loop_kw))
    assert int(resumed.step) == 6
    assert [h.step for h in history] == [6]
    for a, b in zip(jax.tree.leaves(full.params),
                    jax.tree.leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(full.opt_state),
                    jax.tree.leaves(resumed.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
