"""Sharded training step.

``make_train_step`` builds a single jitted SPMD step: params and
optimizer state carry NamedShardings from ``parallel.sharding``, the
batch arrives sharded over (dp, fsdp) x sp, and XLA's partitioner
inserts the FSDP all-gathers, TP psums and gradient reduce-scatters.
Buffers are donated so the step runs in-place in HBM.

There is no hand-rolled gradient-sync code anywhere — on TPU the
collective schedule is the compiler's job (scaling-book recipe); the
framework's job is the shardings.
"""

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_rm_tpu.analysis.jaxcheck import hostsync as _hostsync
from kubeflow_rm_tpu.models import (
    LlamaConfig,
    forward_with_aux,
    init_params,
)
from kubeflow_rm_tpu.ops.attention import kernel_choices
from kubeflow_rm_tpu.ops.flash_attention import flash_tile_counts
from kubeflow_rm_tpu.ops.losses import softmax_cross_entropy
from kubeflow_rm_tpu.parallel.sharding import batch_pspec, param_shardings
from kubeflow_rm_tpu.training.optim import (
    OptimConfig,
    host_device,
    host_put,
    make_offload_optimizer,
    make_optimizer,
)


@dataclass(frozen=True)
class TrainConfig:
    model: LlamaConfig = field(default_factory=LlamaConfig.tiny)
    optim: OptimConfig = field(default_factory=OptimConfig)
    z_loss: float = 1e-4


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any


class _Partition:
    """Split a param tree into trainable/frozen leaf lists by a mask
    (``optim.train_only``): the train step differentiates ONLY the
    trainable list, so frozen weights get neither gradient buffers nor
    optimizer moments — the memory shape LoRA fine-tuning needs."""

    def __init__(self, params, mask_tree):
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.mask = jax.tree_util.tree_leaves(mask_tree)
        assert len(self.mask) == len(leaves)
        if not any(self.mask):
            raise ValueError("train_only matched no parameters")

    def split(self, params):
        leaves = jax.tree_util.tree_leaves(params)
        train = [p for p, m in zip(leaves, self.mask) if m]
        frozen = [p for p, m in zip(leaves, self.mask) if not m]
        return train, frozen

    def combine(self, train, frozen):
        it_t, it_f = iter(train), iter(frozen)
        leaves = [next(it_t) if m else next(it_f) for m in self.mask]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def _partition_for(cfg: TrainConfig, params) -> _Partition | None:
    if cfg.optim.train_only is None:
        return None
    if cfg.optim.train_only != "lora":
        raise ValueError(
            f"unknown train_only={cfg.optim.train_only!r} (only 'lora')")
    from kubeflow_rm_tpu.models.lora import lora_mask
    return _Partition(params, lora_mask(params))


def init_train_state(cfg: TrainConfig, key: jax.Array,
                     params=None) -> TrainState:
    """Fresh state; pass ``params`` to seed from existing weights (an
    HF conversion, or ``models.lora.add_lora`` output for adapter
    training)."""
    if params is None:
        params = init_params(cfg.model, key)
    part = _partition_for(cfg, params)
    if cfg.optim.offload == "optimizer":
        # host-resident layout: {leaf_key: per-leaf chain state}, built
        # leaf-by-leaf on the host device so a 2.7B adam init never
        # materializes mu/nu in HBM (make_offload_optimizer rejects
        # the train_only combination)
        opt_state = make_offload_optimizer(cfg.optim, params).init(params)
    else:
        opt = make_optimizer(cfg.optim)
        if part is None:
            opt_state = opt.init(params)
        else:
            opt_state = opt.init(part.split(params)[0])
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state)


def state_shardings(cfg: TrainConfig, state: TrainState, mesh: Mesh) -> TrainState:
    """NamedSharding pytree for a TrainState: any optimizer sub-tree with
    the params' structure (adam moments, decayed-weights masks) inherits
    the param shardings; scalars (step counts) are replicated."""
    pshard = param_shardings(state.params, mesh)
    replicated = NamedSharding(mesh, P())
    param_treedef = jax.tree_util.tree_structure(state.params)
    param_leaves = jax.tree_util.tree_leaves(state.params)

    def map_node(node):
        try:
            if jax.tree_util.tree_structure(node) == param_treedef:
                # params-shaped state (adam moments) inherits the param
                # shardings leaf-for-leaf — but only where shapes match:
                # adafactor's factored stats share the STRUCTURE while
                # holding row/col vectors, which must stay replicated
                node_leaves = jax.tree_util.tree_leaves(node)
                shard_leaves = [
                    s if getattr(n, "shape", None) == p.shape else replicated
                    for n, p, s in zip(node_leaves, param_leaves,
                                       jax.tree_util.tree_leaves(pshard))
                ]
                return jax.tree_util.tree_unflatten(param_treedef,
                                                    shard_leaves)
        except Exception:
            # fall through to the structural recursion below — but
            # leave a trace, since a silently-unsharded optimizer
            # state is exactly the kind of fault that only shows up
            # as an OOM three steps later
            logging.getLogger("kubeflow_rm_tpu.training").debug(
                "state sharding fast path failed; recursing node "
                "structurally", exc_info=True)
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*(map_node(c) for c in node))
        if isinstance(node, (list, tuple)):
            return type(node)(map_node(c) for c in node)
        if isinstance(node, dict):
            return {k: map_node(v) for k, v in node.items()}
        return replicated

    return TrainState(
        step=replicated,
        params=pshard,
        opt_state=map_node(state.opt_state),
    )


def loss_fn(params, batch, cfg: TrainConfig,
            mesh: Mesh | None = None, n_microbatches: int | None = None):
    # batches come from training.data (pack_documents layout: per-doc
    # restarting positions), so the packed fast path is sound here
    kwargs = dict(positions=batch.get("positions"),
                  segments=batch.get("segments"),
                  packed=batch.get("segments") is not None)
    with kernel_choices() as attention:
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            from kubeflow_rm_tpu.parallel.pipeline import (
                pipeline_forward_with_aux,
            )
            logits, router_aux = pipeline_forward_with_aux(
                params, batch["tokens"], cfg.model, mesh,
                n_microbatches=n_microbatches, **kwargs)
        else:
            logits, router_aux = forward_with_aux(params, batch["tokens"],
                                                  cfg.model, mesh=mesh,
                                                  **kwargs)
    loss, aux = softmax_cross_entropy(logits, batch["labels"],
                                      z_loss=cfg.z_loss)
    if kwargs["packed"] and "flash" in attention:
        # how much of the causal triangle this microbatch's documents
        # left the flash kernels to visit (two reductions over the ids)
        live, causal = flash_tile_counts(batch["segments"])
        aux = dict(aux, flash_tiles_live_share=live / causal)
    if router_aux is not None:
        aux = dict(aux, router_aux=router_aux)
        loss = loss + cfg.model.moe.router_aux_weight * router_aux
    return loss, aux


def make_train_step(cfg: TrainConfig, mesh: Mesh, state: TrainState,
                    batch_keys: tuple = ("tokens", "labels"),
                    n_microbatches: int | None = None,
                    grad_accum: int = 1,
                    offload: str | None = None) -> Callable:
    """Return jitted ``step(state, batch) -> (state, metrics)``.

    ``batch`` maps each of ``batch_keys`` to a (B, T) int32 array laid
    out with ``batch_pspec`` on ``mesh`` — "tokens" and "labels" always,
    plus "positions" and "segments" when training on packed documents
    (see ``training.data.pack_documents``).

    On a mesh with pp > 1 the forward runs the GPipe schedule
    (``parallel.pipeline``); ``n_microbatches`` (default: pp) sets the
    bubble fraction (pp-1)/(n_microbatches+pp-1).

    ``grad_accum`` > 1 splits the global batch into that many
    sequential microbatches under ``lax.scan``, accumulating gradients
    before ONE optimizer update. Two reasons to use it: effective batch
    beyond what HBM fits, and amortizing the optimizer update — on a
    ~1B-param single chip the adam step is pure HBM traffic worth a
    double-digit share of step time, and accumulation divides it by K.
    The per-step loss/grads equal the full-batch computation up to
    accumulation-order rounding (asserted by tests/test_train.py).

    ``offload="optimizer"`` (default: ``cfg.optim.offload``) returns
    the streamed host-offload arm instead: the device runs ONLY the
    grad-accum phase, then gradients stream host-ward in layer-group
    chunks double-buffered against the per-leaf optimizer update on
    the host, and updated params stream back (see
    ``_build_offload_step``). Loss/params match the on-chip arm
    bit-for-bit on one backend (tests/test_offload.py).
    """
    if offload is None:
        offload = cfg.optim.offload
    if offload not in ("none", "optimizer"):
        raise ValueError(f"unknown offload={offload!r} "
                         "(expected 'none' or 'optimizer')")
    if mesh.shape.get("pp", 1) > 1 and n_microbatches is None:
        n_microbatches = mesh.shape["pp"]
    sshard = state_shardings(cfg, state, mesh)
    bshard = {k: NamedSharding(mesh, batch_pspec()) for k in batch_keys}
    mshard = NamedSharding(mesh, P())
    part = _partition_for(cfg, state.params)

    if part is None:
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    else:
        # differentiate ONLY the trainable leaves: the backward never
        # materializes base-weight gradients (dW = h^T g outer products
        # are the dominant bwd memory/flops for a frozen 7B)
        def _loss_trainable(train, frozen, batch, cfg, mesh, n_mb):
            return loss_fn(part.combine(train, frozen), batch, cfg,
                           mesh, n_mb)

        _grad_trainable = jax.value_and_grad(_loss_trainable,
                                             has_aux=True)

        def grad_fn(params, batch, cfg, mesh, n_mb):
            train, frozen = part.split(params)
            return _grad_trainable(train, frozen, batch, cfg, mesh, n_mb)

    def fold(a):
        # interleaved: microbatch m takes rows m, K+m, ... so the fold
        # keeps K replicated and the microbatch dim on the batch
        # sharding with zero resharding traffic (same reasoning as
        # parallel.pipeline's fold)
        if a.shape[0] % grad_accum:
            raise ValueError(
                f"batch {a.shape[0]} not divisible by "
                f"grad_accum={grad_accum}")
        mb = a.shape[0] // grad_accum
        a = a.reshape(mb, grad_accum, *a.shape[1:]).swapaxes(0, 1)
        spec = P(None, *batch_pspec())
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, spec))

    def accumulate(params, batch):
        folded = {k: fold(v) for k, v in batch.items()}

        def body(acc, mbatch):
            (loss, aux), g = grad_fn(params, mbatch, cfg, mesh,
                                     n_microbatches)
            return jax.tree_util.tree_map(jnp.add, acc, g), (loss, aux)

        grad_target = params if part is None else part.split(params)[0]
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), grad_target)
        summed, (losses, auxes) = jax.lax.scan(body, zeros, folded)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum, summed)
        loss = jnp.mean(losses)
        aux = jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), auxes)
        return (loss, aux), grads

    def compute_grads(params, batch):
        if grad_accum > 1:
            return accumulate(params, batch)
        return grad_fn(params, batch, cfg, mesh, n_microbatches)

    if offload == "optimizer":
        return _build_offload_step(cfg, mesh, state, part, compute_grads,
                                   bshard, mshard)

    opt = make_optimizer(cfg.optim)

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        (loss, aux), grads = compute_grads(state.params, batch)
        if part is None:
            target, frozen = state.params, None
        else:
            target, frozen = part.split(state.params)
        updates, opt_state = opt.update(grads, state.opt_state, target)
        target = optax.apply_updates(target, updates)
        params = target if part is None else part.combine(target, frozen)
        gnorm = optax.global_norm(grads)
        metrics = {"loss": loss, "grad_norm": gnorm, **aux}
        return TrainState(step=state.step + 1, params=params,
                          opt_state=opt_state), metrics

    return jax.jit(
        step,
        in_shardings=(sshard, bshard),
        out_shardings=(sshard, mshard),
        donate_argnums=(0,),
    )


#: transfer chunks dispatched beyond the one being consumed — the
#: double-buffer depth of the stream (chunk k updates while k+1..k+2
#: are in flight), and the multiplier in the on-chip stream-slot
#: accounting that memplan's native offload walk reuses
_STREAM_LOOKAHEAD = 2


def _build_offload_step(cfg: TrainConfig, mesh: Mesh, state: TrainState,
                        part, compute_grads, bshard, mshard) -> Callable:
    """The streamed host-offload arm of ``make_train_step``.

    Two phases per step instead of one fused jit:

    1. **Grad phase (device, one jit).** The grad-accum scan plus the
       global grad norm. ``state.params`` is donated and passed
       through, so the scan carry accumulates in place (no
       double-buffered grads tree — the other half of MEMPLAN_r01's
       2.7B diagnosis) and the caller's param buffers alias the
       outputs instead of copying.
    2. **Streaming phase (host).** Gradient and param leaves stream
       host-ward in layer-group chunks (``lax.slice_in_dim`` along the
       stacked-layer axis, ``copy_to_host_async``), double-buffered
       ``_STREAM_LOOKAHEAD`` chunks deep so chunk k+1's transfer rides
       under chunk k's work; when a leaf is assembled on host, its
       per-leaf optimizer update (``OffloadOptimizer.update_leaf`` —
       arithmetically the on-chip chain) runs on the host device and
       the updated leaf is dispatched straight back with the param
       sharding (async H2D). Device-side grad/param leaves are deleted
       as their last chunk dispatches, so on-chip residency beyond the
       grad phase stays bounded by the stream slot.

    The update is leaf-granular while transfers are chunk-granular:
    adafactor's block-RMS clips reduce over whole leaves, so per-chunk
    updates would change the arithmetic — per-leaf updates keep the
    offload arm bit-identical to the on-chip arm on a given backend.

    The step donates ``state`` in the same sense the on-chip jit does:
    param and optimizer buffers are consumed (donated into the grad
    phase / deleted after streaming), so the caller must rebind
    ``state`` from the return value.
    """
    from collections import deque

    if part is not None:
        raise ValueError("offload='optimizer' does not compose with "
                         "train_only — see make_offload_optimizer")
    if mesh.shape.get("pp", 1) > 1:
        raise ValueError("offload='optimizer' targets the single-chip "
                         "memory wall; pp meshes keep the update "
                         "on-chip (state is already sharded)")
    opt = make_offload_optimizer(cfg.optim, state.params)
    keys = opt.keys
    if not (isinstance(state.opt_state, dict)
            and set(state.opt_state) == set(keys)):
        raise ValueError(
            "state.opt_state is not the host-offload layout; build the "
            "state with OptimConfig(offload='optimizer') so "
            "init_train_state lays it out host-resident")

    flat, ptreedef = jax.tree_util.tree_flatten(state.params)
    shapes = [tuple(p.shape) for p in flat]
    dtypes = [jnp.dtype(p.dtype) for p in flat]
    pshard = param_shardings(state.params, mesh)
    pshard_leaves = jax.tree_util.tree_leaves(pshard)

    # layer-group chunk plan: stacked (L, ...) leaves stream in slices
    # of offload_chunk_layers along axis 0; flat leaves (embedding,
    # norms) stream whole
    chunk_layers = max(1, cfg.optim.offload_chunk_layers)
    chunks: list[list[tuple[int, int]] | None] = []
    for shp in shapes:
        if len(shp) >= 3 and shp[0] > 1:
            chunks.append([(a, min(a + chunk_layers, shp[0]))
                           for a in range(0, shp[0], chunk_layers)])
        else:
            chunks.append(None)

    def _chunk_bytes(i, r) -> int:
        shp, item = shapes[i], dtypes[i].itemsize
        rows = shp[0] if r is None else (r[1] - r[0])
        per_row = item
        for d in shp[1:]:
            per_row *= d
        return rows * per_row if shp else item

    work: list[tuple[int, tuple[int, int] | None, bool]] = []
    for i in range(len(flat)):
        if chunks[i] is None:
            work.append((i, None, True))
        else:
            for j, r in enumerate(chunks[i]):
                work.append((i, r, j == len(chunks[i]) - 1))
    max_pair = max((2 * _chunk_bytes(i, r) for i, r, _ in work), default=0)
    # grad + param slices per chunk, one consumed + LOOKAHEAD in flight
    stream_slot_bytes = (1 + _STREAM_LOOKAHEAD) * max_pair

    def grad_phase(params, batch):
        (loss, aux), grads = compute_grads(params, batch)
        gnorm = optax.global_norm(grads)
        return params, grads, loss, gnorm, aux

    grad_phase_j = jax.jit(
        grad_phase,
        in_shardings=(pshard, bshard),
        out_shardings=(pshard, pshard, mshard, mshard, mshard),
        donate_argnums=(0,),
    )

    @partial(jax.jit, static_argnames=("key",), donate_argnums=(0,))
    def _leaf_update(opt_leaf_state, grad, param, gnorm, *, key):
        return opt.update_leaf(key, opt_leaf_state, grad, param, gnorm)

    host = host_device()

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        params_thru, grads, loss, gnorm, aux = grad_phase_j(
            state.params, batch)
        new_step = state.step + 1
        g_leaves = jax.tree_util.tree_leaves(grads)
        p_leaves = jax.tree_util.tree_leaves(params_thru)
        new_p_leaves: list = [None] * len(p_leaves)
        new_opt: dict = {}
        blocked = 0.0
        t_stream = time.perf_counter()
        with _hostsync.sanctioned("train.offload_stream"):
            inflight: deque = deque()
            pos = 0

            def dispatch_next():
                nonlocal pos
                i, r, last = work[pos]
                pos += 1
                g, p = g_leaves[i], p_leaves[i]
                if r is None:
                    gsl, psl = g, p
                else:
                    gsl = jax.lax.slice_in_dim(g, r[0], r[1])
                    psl = jax.lax.slice_in_dim(p, r[0], r[1])
                gsl.copy_to_host_async()
                psl.copy_to_host_async()
                if r is not None and last:
                    # the slices carry the data from here on: free the
                    # device-resident source leaves so on-chip residency
                    # past the grad phase is just the stream slot
                    g.delete()
                    p.delete()
                return gsl, psl

            for _ in range(min(1 + _STREAM_LOOKAHEAD, len(work))):
                inflight.append(dispatch_next())

            t1 = time.perf_counter()
            gnorm_host = jax.device_put(np.asarray(gnorm), host)
            blocked += time.perf_counter() - t1

            for i, key in enumerate(keys):
                n_chunks = 1 if chunks[i] is None else len(chunks[i])
                parts_g, parts_p = [], []
                for _ in range(n_chunks):
                    gsl, psl = inflight.popleft()
                    t1 = time.perf_counter()
                    parts_g.append(np.asarray(gsl))
                    parts_p.append(np.asarray(psl))
                    blocked += time.perf_counter() - t1
                    if pos < len(work):
                        inflight.append(dispatch_next())
                gh = (parts_g[0] if n_chunks == 1
                      else np.concatenate(parts_g, axis=0))
                ph = (parts_p[0] if n_chunks == 1
                      else np.concatenate(parts_p, axis=0))
                leaf_state = jax.tree_util.tree_map(
                    host_put, state.opt_state[key])
                new_p_host, new_opt[key] = _leaf_update(
                    leaf_state,
                    jax.device_put(gh, host),
                    jax.device_put(ph, host),
                    gnorm_host, key=key)
                # async H2D: the next leaf's transfers and update
                # overlap this dispatch
                new_p_leaves[i] = jax.device_put(new_p_host,
                                                 pshard_leaves[i])
                if chunks[i] is None:
                    # whole-leaf transfers: the host copy exists, free
                    # the device source now rather than at step exit
                    g_leaves[i].delete()
                    p_leaves[i].delete()
        stream_wall = time.perf_counter() - t_stream
        params = jax.tree_util.tree_unflatten(ptreedef, new_p_leaves)
        metrics = {
            "loss": loss, "grad_norm": gnorm, **aux,
            "offload_transfer_ms": blocked * 1e3,
            "offload_overlap_frac": (max(0.0, 1.0 - blocked / stream_wall)
                                     if stream_wall > 0 else 0.0),
        }
        return TrainState(step=new_step, params=params,
                          opt_state=new_opt), metrics

    # introspection surface: memplan's native offload walk estimates
    # the grad phase and adds the stream slot; tests assert the plan
    step.grad_phase = grad_phase_j
    step.stream_slot_bytes = stream_slot_bytes
    step.chunk_plan = dict(zip(keys, chunks))
    step.offload = "optimizer"
    return step


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    """Device-put a host batch onto the mesh with the standard layout."""
    s = NamedSharding(mesh, batch_pspec())
    return {k: jax.device_put(v, s) for k, v in batch.items()}
