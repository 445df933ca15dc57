"""Mixture-of-experts layer with expert parallelism over the ``ep`` axis.

TPU-first design — dense dispatch, not gather/scatter:

- Routing produces a static-shaped dispatch tensor (tokens, E, C) and
  the expert FFN runs as one batched matmul over the expert dim. No
  ragged shapes, no data-dependent control flow: everything tiles onto
  the MXU and jit-compiles once (GShard/Switch formulation).
- Expert parallelism is pure sharding: the expert dim of the weights
  carries ``ep`` (``sharding._MIXTRAL_RULES``) and XLA's SPMD
  partitioner turns the dispatch/combine einsums into the all-to-alls
  an expert-parallel layer needs — the scaling-book recipe, in contrast
  to the reference's hand-written NCCL all-to-all (SURVEY.md §2.6 lists
  EP as an in-image capability to supply).
- Capacity-dropped tokens fall through on the residual path (standard
  Switch behavior); the auxiliary load-balancing loss keeps routing
  uniform so drops stay rare.
"""

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.ops import expert_ffn


@dataclass(frozen=True)
class MoeConfig:
    n_experts: int = 8
    top_k: int = 2
    # per-expert slots = ceil(top_k * tokens * capacity_factor / E)
    capacity_factor: float = 1.25
    # weight of the load-balancing aux loss in the training objective
    router_aux_weight: float = 0.01


def expert_capacity(cfg: MoeConfig, n_tokens: int) -> int:
    import math
    cap = math.ceil(cfg.top_k * n_tokens * cfg.capacity_factor /
                    cfg.n_experts)
    return max(cap, 1)


def route(router_logits: jax.Array, cfg: MoeConfig, capacity: int):
    """Top-k routing with per-expert capacity.

    Args:
      router_logits: (N, E) fp32.
    Returns:
      dispatch: (N, E, C) 0/1 — token n occupies slot c of expert e.
      combine: (N, E, C) fp32 — dispatch weighted by the (renormalized)
        top-k gate.
      aux_loss: scalar load-balancing loss (Switch formulation,
        ``E * Σ_e fraction_routed_e * mean_prob_e``).
    """
    N, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)  # (N, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # one-hot per slot; slot 0 (the argmax choice) claims capacity
    # before slot 1 across ALL tokens, then ties break by token order —
    # priority is (slot, token), matching the GShard schedule
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (N, k, E)
    slot_major = onehot.transpose(1, 0, 2).reshape(cfg.top_k * N, E)
    pos = jnp.cumsum(slot_major, axis=0) - 1  # position within expert
    pos = pos.reshape(cfg.top_k, N, E).transpose(1, 0, 2)  # (N, k, E)
    pos = jnp.sum(pos * onehot, axis=-1)  # (N, k) slot index
    fits = pos < capacity

    slot_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
    slot_onehot = slot_onehot * fits[..., None]
    # (N, k, E, C): expert choice x slot
    dispatch_k = onehot[..., None].astype(jnp.float32) * \
        slot_onehot[:, :, None, :]
    dispatch = jnp.sum(dispatch_k, axis=1)  # (N, E, C)
    combine = jnp.sum(
        dispatch_k * gate_vals[..., None, None], axis=1)

    # load balance: fraction of tokens whose TOP choice is e x mean
    # router prob on e (differentiable through probs)
    top1 = onehot[:, 0, :].astype(jnp.float32)
    frac_routed = jnp.mean(top1, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(frac_routed * mean_prob)
    return dispatch, combine, aux_loss


def moe_param_shapes(cfg: MoeConfig, dim: int, hidden: int) -> dict:
    E = cfg.n_experts
    return {
        "router": (dim, E),
        "moe_gate": (E, dim, hidden),
        "moe_up": (E, dim, hidden),
        "moe_down": (E, hidden, dim),
    }


def moe_ffn(params: dict, x: jax.Array, cfg: MoeConfig,
            dtype: Any = jnp.bfloat16):
    """SwiGLU expert FFN. x: (B, T, D) -> ((B, T, D), aux_loss).

    The (E, C, D) expert batch is where EP bites: with w_* sharded
    P(..., "ep", ...) the dispatch einsum becomes an all-to-all and the
    three expert matmuls run ep-parallel.
    """
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    # router in fp32: tiny matmul, and routing decisions should not
    # flip with bf16 rounding
    logits = xf.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    capacity = expert_capacity(cfg, N)
    dispatch, combine, aux = route(logits, cfg, capacity)

    from jax.ad_checkpoint import checkpoint_name

    xc = xf.astype(dtype)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype), xc)
    # tag with the same names as the dense MLP so the named remat
    # policies ("mlp", "attn+mlp") buy the same HBM/recompute trade
    # for the expert FFN
    gate = checkpoint_name(
        jnp.einsum("ecd,edf->ecf", expert_in,
                   params["moe_gate"].astype(dtype)), "mlp_gate")
    up = checkpoint_name(
        jnp.einsum("ecd,edf->ecf", expert_in,
                   params["moe_up"].astype(dtype)), "mlp_up")
    h = jax.nn.silu(gate) * up
    expert_out = jnp.einsum("ecf,efd->ecd", h,
                            params["moe_down"].astype(dtype))
    out = jnp.einsum("nec,ecd->nd", combine.astype(dtype), expert_out)
    return out.reshape(B, T, D), aux


# ---------------------------------------------------------------------------
# dropless experts, a share of them held here
# ---------------------------------------------------------------------------


def route_sigmoid_topk(h: jax.Array, router_w: jax.Array, bias: jax.Array,
                       top_k: int, scale: float):
    """Sigmoid routing over every expert the router names: ``h`` (N, D)
    -> (expert ids (N, k) int32, weights (N, k) float32). The ``top_k``
    largest of ``sigmoid(h W) + bias`` are chosen (the bias steers the
    choice only), weighted by their own scores normalised to sum to
    ``scale``. Float32 at full precision: a routing decision should not
    flip with the compute dtype's rounding."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               router_w.astype(jnp.float32),
                               precision="highest"))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), w


def held_experts_ffn(h: jax.Array, router_w: jax.Array, bias: jax.Array,
                     up: jax.Array, down: jax.Array, first: int,
                     top_k: int, scale: float, *,
                     expert_in: jax.Array | None = None,
                     live: jax.Array | None = None):
    """The part of a dropless expert layer that the experts held here
    give: squared-ReLU experts ``up`` (held, d, f) and ``down``
    (held, f, d), which are experts ``first .. first + held`` of the
    ``router_w.shape[1]`` the router chooses among.

    Every token of ``h`` (N, D) is routed over all experts; the
    assignments whose expert lies in the held range are computed, by
    one of two dispatches chosen by the static row count: above
    ``_FEW_ROWS`` the assignments are sorted by expert and run through
    two grouped matmuls (``_sorted_grouped``); at or under it all rows
    meet each expert that met a token, whose matrices are streamed
    once — by ``ops/expert_ffn.py``'s pallas kernel on a one-device
    TPU program whose widths tile, by a loop in plain XLA
    (``_active_experts_loop``) elsewhere; either way an expert nobody
    chose is not read. Shapes are static: the N x top_k
    assignments are all carried, those of other chips' experts (and of
    rows ``live`` (N,) marks dead: padding, empty slots) belong to no
    group. No capacity, so no token is dropped whatever the imbalance.
    On one chip the layer runs without its exchange: what the absent
    experts would add is left out.

    The experts read ``expert_in`` (N, d) where given (a latent
    projection of ``h``), else ``h``. Returns the (N, d) partial sum in
    ``expert_in``'s dtype and ``(assignments held, experts with at
    least one)`` as int32 scalars."""
    x = h if expert_in is None else expert_in
    N, held = x.shape[0], up.shape[0]
    idx, w = route_sigmoid_topk(h, router_w, bias, top_k, scale)
    local = idx - first
    mine = (local >= 0) & (local < held)
    if live is not None:
        mine = mine & live[:, None]
    group = jnp.where(mine, local, held)                      # (N, k)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[
        group.reshape(-1)].add(1)[:held]
    counts = (jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32))
    if N <= _FEW_ROWS:
        # (held, N): row n's weight for each held expert; the experts
        # that met a token, ascending, and their number
        dense = jnp.zeros((held, N), jnp.float32).at[
            group, jnp.arange(N)[:, None]].add(w, mode="drop")
        active = jnp.nonzero(sizes > 0, size=held, fill_value=0)[0]
        few = (expert_ffn.active_experts_ffn
               if expert_ffn.takes_kernel(x, up) else _active_experts_loop)
        out = few(x, dense, active, counts[1], up, down)
    else:
        out = _sorted_grouped(x, group.reshape(-1), w.reshape(-1), sizes,
                              up, down, top_k)
    return out.astype(x.dtype), counts


#: at or under this many rows (a decode step's slots, a prompt's
#: bucket) every row meets each active expert, with a weight of 0
#: where it did not choose it, and an expert's matrices are streamed
#: once: cheaper than sorting the N x top_k assignments into a grouped
#: matmul's tiles while the multiplies stay under the reads. Swept on
#: the v5e at the Nemotron cut's widths and routing (22 of 512 experts
#: a row, 128 held; PERF.md section 6, PR 41), a layer's call in ms at
#: 32 / 128 / 256 / 512 / 1024 rows: the kernel 1.6 / 1.9 / 2.1 / 3.9 /
#: 7.7, the loop 2.1 / 2.6 / 3.0 / 4.6 / 8.5, the grouped matmuls 3.6 /
#: 5.4 / 6.9 / 7.5 / 9.0. 1024 is the most rows swept (the longest
#: prompt bucket of the serving cell), not where the two meet: the
#: kernel's time doubles with the rows from 512 on (the MXU, 0.06 us a
#: row and expert) and the grouped matmuls' grows by a fifth, so they
#: would meet somewhere under 2048.
_FEW_ROWS = 1024


def _sorted_grouped(x, group, w, sizes, up, down, top_k):
    """Many rows: the assignments sorted by expert, two grouped matmuls
    (``lax.ragged_dot``), the sum over a token's choices. ``group``
    (N k,) is the held expert of an assignment, ``held`` where it has
    none; ``sizes`` (held,) the assignments an expert."""
    A = group.shape[0]
    order = jnp.argsort(group)                                # stable
    valid = (jnp.arange(A) < jnp.sum(sizes))[:, None]
    rows = x[order // top_k]
    a = jax.lax.ragged_dot(rows, up.astype(x.dtype), sizes,
                           preferred_element_type=jnp.float32)
    # rows past the last group belong to nobody: whatever the grouped
    # matmul left there is replaced, not scaled
    a = jnp.where(valid, jnp.square(jax.nn.relu(a)), 0.0).astype(x.dtype)
    y = jax.lax.ragged_dot(a, down.astype(x.dtype), sizes,
                           preferred_element_type=jnp.float32)
    y = jnp.where(valid, y, 0.0) * w[order][:, None]
    # back to (token, choice) order, then the sum over a token's choices
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(A, dtype=order.dtype))
    return jnp.sum(y[back].reshape(-1, top_k, y.shape[-1]), axis=1)


def _active_experts_loop(x, dense, active, n, up, down):
    """Few rows, plain XLA (off the chip, a sharded call; the reference
    of ``ops/expert_ffn.py``'s kernel, whose arguments it takes): a
    loop over the ``n`` experts ``active`` lists, those that met a
    token (an expert nobody chose is never read). Each streams its two
    matrices once; all N rows are multiplied, which costs nothing
    beside the read, and a row's result counts with the weight
    ``dense`` (held, N) gives it for that expert, 0 where it did not
    choose it."""
    def one(i, acc):
        e = active[i]
        a = jnp.dot(x, jax.lax.dynamic_index_in_dim(up, e, keepdims=False)
                    .astype(x.dtype), preferred_element_type=jnp.float32)
        a = jnp.square(jax.nn.relu(a)).astype(x.dtype)
        y = jnp.dot(a, jax.lax.dynamic_index_in_dim(down, e, keepdims=False)
                    .astype(x.dtype), preferred_element_type=jnp.float32)
        return acc + jax.lax.dynamic_index_in_dim(
            dense, e, keepdims=False)[:, None] * y

    return jax.lax.fori_loop(
        0, n, one, jnp.zeros((x.shape[0], down.shape[-1]), jnp.float32))
