"""Optimizer construction (optax).

AdamW with a no-decay mask on norms/embeddings, warmup+cosine schedule,
global-norm clipping. ``mu_dtype`` defaults to bf16: on a 16 GiB v5e
chip the first-moment buffer is the difference between fitting a ~1B
model and not; the second moment stays fp32 for stability.

``factored=True`` swaps adam's per-parameter moments for adafactor's
factored second moment (row/col RMS vectors, ~O(in+out) per matrix
instead of O(in*out)) with no first moment — the optimizer that was
built for exactly this hardware constraint (TPU HBM; Shazeer & Stern
2018). Optimizer state drops from ~6 bytes/param to ~0, which is what
lets a ~3B model FULL-fine-tune on one 16 GiB v5e
(params 2B + transient grads 2B ≈ 4 bytes/param); see bench.py
--optim adafactor and BENCH_SWEEP_r05.json's mfu-vs-scale table.

``offload="optimizer"`` is the next rung past that wall (MEMPLAN_r01):
optimizer state lives in HOST memory and the update itself runs on the
host, so the chip holds only params + the grad-accum carry + one
microbatch's workspace. The policy here is the *optimizer half* of the
design: :func:`make_offload_optimizer` decomposes the exact
``make_optimizer`` chain into per-leaf chains (everything after the
global-norm clip is leaf-local; the clip itself needs one scalar — the
global norm — which the train step computes on device and threads
through), so the streamed update is arithmetically identical to the
on-chip one, leaf for leaf. Host placement is plain CPU-backend arrays
(which *are* host RAM), so the mechanism is testable on the CPU CI
host.
"""

from dataclasses import dataclass

import jax.numpy as jnp
import optax


@dataclass(frozen=True)
class OptimConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    mu_dtype: str = "bfloat16"
    # factored second moment (adafactor), no first moment: near-zero
    # optimizer state for the multi-billion-single-chip memory shape
    factored: bool = False
    # dims below this stay unfactored (optax default; tests lower it —
    # every real model dim here is >= 2048)
    factored_min_dim: int = 128
    # "lora": train only adapter leaves (models.lora); the train step
    # then neither computes gradients nor stores moments for the frozen
    # base — the memory shape that fits 7B fine-tuning on one chip
    train_only: str | None = None
    # "optimizer": moments/stats live in host memory and the update is
    # streamed (training.train's offload arm) — the MEMPLAN_r01 recipe
    # that fits 2.7B full-FT on the chip that OOMs at 18.34 GB today
    offload: str = "none"
    # layer-group size for the streamed transfer chunks: stacked
    # (L, ...) leaves move device->host in slices of this many layers,
    # double-buffered, so the on-chip stream slot stays bounded
    offload_chunk_layers: int = 4


def _decay_mask(params):
    import jax

    def mask(path, leaf):
        name = "/".join(p.key for p in path if hasattr(p, "key"))
        return not ("norm" in name or name.startswith("embed"))

    return jax.tree_util.tree_map_with_path(mask, params)


def _make_schedule(cfg: OptimConfig):
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps,
        decay_steps=max(cfg.total_steps, cfg.warmup_steps + 1),
        end_value=cfg.learning_rate * 0.1,
    )


def _make_scaler(cfg: OptimConfig) -> optax.GradientTransformation:
    if cfg.factored:
        # the full adafactor update rule (optax.adafactor's chain):
        # factored RMS normalization, block-RMS update clipping, and
        # the relative (parameter-scale) step size — without the last
        # two the RMS-normalized update is O(1) per element and walks
        # small-init weights straight out of their basin
        return optax.chain(
            optax.scale_by_factored_rms(
                decay_rate=cfg.b2,
                min_dim_size_to_factor=cfg.factored_min_dim),
            optax.clip_by_block_rms(1.0),
            optax.scale_by_param_block_rms(),
        )
    return optax.scale_by_adam(
        b1=cfg.b1, b2=cfg.b2, mu_dtype=jnp.dtype(cfg.mu_dtype))


def make_optimizer(cfg: OptimConfig) -> optax.GradientTransformation:
    schedule = _make_schedule(cfg)
    return optax.chain(
        optax.clip_by_global_norm(cfg.grad_clip),
        _make_scaler(cfg),
        optax.add_decayed_weights(cfg.weight_decay, mask=_decay_mask),
        optax.scale_by_schedule(lambda step: -schedule(step)),
    )


# ---------------------------------------------------------------------------
# host-offload policy: per-leaf chains + host placement
# ---------------------------------------------------------------------------

def host_device():
    """The device whose memory is host RAM: the CPU backend's device
    (present alongside TPU/GPU backends, and the only device on the CI
    host). Optimizer state committed here is host-resident on every
    platform. Raises where the process has no CPU backend (e.g.
    ``JAX_PLATFORMS=tpu``): the accelerator is not a place to offload
    to."""
    import jax
    return jax.local_devices(backend="cpu")[0]


def host_put(x):
    """Commit a concrete array to host memory (CPU backend); abstract
    values (eval_shape tracers) pass through so the offload state
    layout stays shape-traceable for memplan and checkpoint targets."""
    import jax
    if isinstance(x, jax.core.Tracer) or not hasattr(x, "dtype"):
        return x
    return jax.device_put(x, host_device())


def _leaf_name(path) -> str:
    # "." join (orbax-safe): params are nested dicts, so every path
    # entry is a DictKey; indices cover registered-dataclass fields
    return ".".join(str(getattr(p, "key", getattr(p, "idx", "?")))
                    for p in path)


class OffloadOptimizer:
    """The ``make_optimizer`` chain, decomposed for streaming.

    Everything after the global-norm clip is leaf-local (adam moments,
    adafactor's factored stats and its block-RMS clips, the decay mask,
    the schedule), so each param leaf gets its own optax chain over a
    one-entry ``{"leaf": x}`` subtree and its own state, updateable the
    moment that leaf's gradient lands on host. The global-norm clip is
    the one cross-leaf coupling: its only input beyond the leaf is the
    scalar global norm, which the device grad phase computes and the
    train step threads into :meth:`update_leaf` — the arithmetic there
    mirrors ``optax.clip_by_global_norm`` operation for operation, so
    the composition is the on-chip update exactly.
    """

    def __init__(self, cfg: OptimConfig, params):
        import jax
        self.cfg = cfg
        flat, self.treedef = jax.tree_util.tree_flatten_with_path(params)
        self.keys = tuple(_leaf_name(p) for p, _ in flat)
        if len(set(self.keys)) != len(self.keys):
            raise ValueError("param leaf paths do not join uniquely")
        decay = jax.tree_util.tree_leaves(_decay_mask(params))
        schedule = _make_schedule(cfg)
        self._chains = {
            k: optax.chain(
                _make_scaler(cfg),
                optax.add_decayed_weights(cfg.weight_decay,
                                          mask={"leaf": d}),
                optax.scale_by_schedule(
                    lambda step, _s=schedule: -_s(step)),
            )
            for k, d in zip(self.keys, decay)
        }

    def chain(self, key: str) -> optax.GradientTransformation:
        return self._chains[key]

    def init(self, params) -> dict:
        """Host-resident state: ``{leaf_key: per-leaf chain state}`` in
        param flatten order (concrete leaves are committed to host
        memory; abstract ones trace through for eval_shape)."""
        import jax
        leaves = jax.tree_util.tree_leaves(params)
        return {k: self._chains[k].init({"leaf": host_put(p)})
                for k, p in zip(self.keys, leaves)}

    def update_leaf(self, key: str, leaf_state, grad, param, gnorm):
        """One leaf's full update: global-norm clip (mirroring
        ``optax.clip_by_global_norm``'s exact arithmetic against the
        precomputed ``gnorm``), then the leaf's chain, then
        ``apply_updates``. Returns ``(new_param, new_leaf_state)``."""
        import jax
        max_norm = self.cfg.grad_clip
        trigger = jnp.squeeze(gnorm < max_norm)
        clipped = jax.lax.select(
            trigger, grad, (grad / gnorm.astype(grad.dtype)) * max_norm)
        updates, new_state = self._chains[key].update(
            {"leaf": clipped}, leaf_state, {"leaf": param})
        new_param = optax.apply_updates({"leaf": param}, updates)["leaf"]
        return new_param, new_state


def make_offload_optimizer(cfg: OptimConfig, params) -> OffloadOptimizer:
    if cfg.offload != "optimizer":
        raise ValueError(f"offload policy is {cfg.offload!r}, expected "
                         "'optimizer'")
    if cfg.train_only is not None:
        raise ValueError("offload='optimizer' does not compose with "
                         "train_only (LoRA states are small enough to "
                         "stay on-chip)")
    return OffloadOptimizer(cfg, params)
