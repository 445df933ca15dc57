"""Flash attention — pallas TPU kernels for the hot op, forward and backward.

Round 1 materialized a (B, H, T, T) score tensor per layer
(``ops/attention.py``), which caps usable context and burns HBM
bandwidth on the one tensor XLA cannot fuse away. This module is the
promised slot-in (VERDICT "weak" #5): a blockwise online-softmax
forward in pallas — scores never leave VMEM — plus a blockwise pallas
backward from saved logsumexp residuals.

Design (pallas_guide.md patterns):
- grid = (batch·heads, q_blocks, kv_blocks), kv innermost and marked
  "arbitrary" so the (m, l, acc) VMEM scratch carries across kv steps;
  the output block writes once on the final kv step.
- **Tile skipping**: a tile in which no query attends any key is
  neither multiplied nor fetched. Without segment ids that is the
  causal test alone (fully-future kv blocks, ~half the MXU work). With
  segment ids a small table, made from them in plain ``jax.numpy``
  (``live_tiles``), says per batch row and tile whether the causal
  test passes AND the q block's range of ids overlaps the kv block's:
  disjoint ranges hold no equal ids, so the test is sound for any ids
  and tight for rows whose ids rise (``pack_documents``). The table
  reaches the kernels by scalar prefetch; ``pl.when`` reads it, and the
  index maps send a tile that is not live to the block of the nearest
  live one, so the pipeline sees a repeated index and issues no DMA.
  A skipped tile is exactly a no-op of the arithmetic (``p`` zeroed
  under the mask, ``m_new = m_prev``, ``corr = 1``).
- GQA without repetition: q is laid out (B·KVH·G, T, D) while k/v stay
  (B·KVH, T, D); the kv index map divides by G, so repeated heads are
  a VMEM aliasing trick, not an HBM copy.
- Backward is two pallas kernels (``_flash_bwd_pallas``: dq over a
  (BH, nq, nk) grid, dk/dv over (BH, nk, nq)) using the softmax
  residual lse = m + log l — standard flash-attention calculus,
  O(T·block) memory, MXU-shaped matmuls, the forward's tile skipping.
  The blockwise XLA scan it replaced (``_flash_bwd_xla``) is kept as
  the reference behind ``BACKWARD_IMPL``.

Semantics: causal over LOCAL indices + optional segment ids. This is
exactly the packed-documents contract (``training/data.pack_documents``):
within a row, positions rise monotonically inside each document and the
segment mask removes cross-document attention, so local-causal ∧
same-segment ≡ position-causal ∧ same-segment. Callers with truly
non-local positions (ring attention shards) use the XLA path or the
ring schedule in ``parallel/ring_attention.py``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30

# Without segment ids: 1024 blocks measured best on v5e for the
# bench1b shapes (53.4% MFU vs 51.1% at 512, 44.0% at 256, with the
# pallas backward): fewer, bigger MXU panels beat finer-grained causal
# skipping. With segment ids the tile also decides how much of the
# triangle the documents leave, and 512 x 1024 (q x kv) measured best
# at 1 x 32 heads x 4096 x 128, 8 kv heads, on rows of log-normal
# documents of 427 tokens at the mean (forward + dq + dk/dv of one
# call, ms on the v5e, two sets of 16 rows; PERF.md section 6, PR 39):
#
#     tile       1024x1024  512x1024  1024x512  512x512  256x512  256x256
#     live share   0.73       0.61      0.62      0.49     0.43     0.34
#     ms          4.65-4.72  4.39-4.65  4.69      4.56-4.71 5.66-5.88 8.64-8.85
#
# (the parent, every causal tile of 1024: 6.18-6.20). A finer tile
# leaves less area and loses it again to what a grid step costs whatever
# its size: about 0.7 us a live step and 0.25 us a skipped one in the
# forward, beside 1.2 us for each 512 x 512 of area.
# ``pick_block`` degrades to the largest divisor of T so sequence
# lengths that are multiples of 128 but not of the block (1280, 1536,
# ...) stay on the kernel.
#
# KFRM_FLASH_BLOCK overrides every default (KFRM_FLASH_BLOCK_Q/_K win
# for asymmetric grids) — the bench sweep's knob; code callers pass
# block_q/block_k explicitly.


def _default_block(axis: str, measured: int) -> int:
    return int(os.environ.get(
        f"KFRM_FLASH_BLOCK_{axis}",
        os.environ.get("KFRM_FLASH_BLOCK", measured)))


DEFAULT_BLOCK_Q = _default_block("Q", 1024)
DEFAULT_BLOCK_K = _default_block("K", 1024)
PACKED_BLOCK_Q = _default_block("Q", 512)
PACKED_BLOCK_K = _default_block("K", 1024)


def tile_for(T: int, segmented: bool, block_q: int | None = None,
             block_k: int | None = None) -> tuple[int, int]:
    """The (q, kv) tile a length-T call runs at: what was asked for,
    else the measured default for a call that carries segment ids or
    for one that does not, each through ``pick_block`` (so 0 where
    nothing divides T)."""
    if block_q is None:
        block_q = PACKED_BLOCK_Q if segmented else DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = PACKED_BLOCK_K if segmented else DEFAULT_BLOCK_K
    return pick_block(block_q, T), pick_block(block_k, T)


def pick_block(preferred: int, T: int) -> int:
    """Block size for a length-T sequence: the preferred block when it
    divides T (explicit requests, incl. sub-128 test blocks, are
    honored), else the largest 128-multiple divisor of T. Returns 0
    when no VMEM-safe block exists (long T with no such divisor) — the
    caller must reject rather than launch a full-length score block."""
    b = min(preferred, T)
    if T % b == 0:
        return b
    b = (b // 128) * 128
    while b >= 128:
        if T % b == 0:
            return b
        b -= 128
    return T if T < 128 else 0


# ---------------------------------------------------------------------
# which tiles the segment ids leave
# ---------------------------------------------------------------------

def _causal_tiles(nq: int, nk: int, block_q: int, block_k: int):
    """(nq, nk) bool, the kernels' causal test: a kv block strictly in
    the future of every query row of the q block holds no work."""
    i = jnp.arange(nq)[:, None]
    j = jnp.arange(nk)[None, :]
    return j * block_k <= i * block_q + (block_q - 1)


def _window_tiles(nq: int, nk: int, block_q: int, block_k: int,
                  window: int):
    """(nq, nk) bool, the causal test with a window: a kv block whose
    last key lies ``window`` or more before every query row of the q
    block holds no work either (query ``p`` reads keys ``p - window + 1
    .. p``)."""
    i = jnp.arange(nq)[:, None]
    j = jnp.arange(nk)[None, :]
    return (_causal_tiles(nq, nk, block_q, block_k)
            & ((j + 1) * block_k - 1 > i * block_q - window))


def live_tiles(segq, segkv, block_q: int, block_k: int,
               causal: bool = True) -> jax.Array:
    """(B, nq, nk) bool: may any query of q block i attend any key of
    kv block j? True iff the tile passes the causal test and the two
    blocks' ranges of segment ids overlap. Disjoint ranges hold no
    equal ids, so no tile with an attending pair is ever dropped,
    whatever the ids (padding's 0, ids out of order); for ids that rise
    along the row (``pack_documents``) overlapping ranges share an id,
    so no tile is kept in vain either."""
    B, T = segq.shape
    q = segq.reshape(B, T // block_q, block_q)
    kv = segkv.reshape(B, segkv.shape[1] // block_k, block_k)
    live = ((q.min(-1)[:, :, None] <= kv.max(-1)[:, None, :])
            & (kv.min(-1)[:, None, :] <= q.max(-1)[:, :, None]))
    if causal:
        live &= _causal_tiles(q.shape[1], kv.shape[1], block_q, block_k)
    return live


def flash_tile_counts(segment_ids_q, segment_ids_kv=None, *,
                      causal: bool = True, block_q: int | None = None,
                      block_k: int | None = None):
    """(live, causal): how many tiles the kernels run for these (B, T)
    segment ids — the table they are handed, counted — and how many
    the causal test alone would leave, over all rows. The tile
    defaults to the one ``flash_attention`` picks for a call with
    segment ids."""
    if segment_ids_kv is None:
        segment_ids_kv = segment_ids_q
    B, T = segment_ids_q.shape
    block_q, block_k = tile_for(T, True, block_q, block_k)
    live = live_tiles(segment_ids_q, segment_ids_kv, block_q, block_k,
                      causal)
    nq, nk = live.shape[1:]
    return jnp.sum(live), B * (
        jnp.sum(_causal_tiles(nq, nk, block_q, block_k)) if causal
        else nq * nk)


def _nearest_live(live, axis: int) -> jax.Array:
    """int32, ``live``'s shape: along ``axis`` each tile's own index
    where it is live, else the index of the last live tile before it,
    else (none before) of the first live one. A tile is live iff the
    entry equals its index; a block index map that reads the entry
    repeats an index over every run of skipped tiles."""
    idx = jax.lax.broadcasted_iota(jnp.int32, live.shape, axis)
    last = jax.lax.cummax(jnp.where(live, idx, -1), axis=axis)
    first = jnp.argmax(live, axis=axis, keepdims=True).astype(jnp.int32)
    return jnp.where(last < 0, first, last)


def _fetch_entry(fetch_ref, heads: int, b, outer, inner, n_outer,
                 n_inner):
    """Grid step (b, outer, inner)'s entry of a flattened
    (B, n_outer, n_inner) ``_nearest_live`` table."""
    return fetch_ref[((b // heads) * n_outer + outer) * n_inner + inner]


def _inner_block(heads: int, n_outer: int, n_inner: int):
    """For index maps: the block a grid step (b, outer, inner) fetches
    along its innermost axis. With a table (the maps' last argument
    under scalar prefetch) a skipped tile repeats the nearest live
    one's block, so its operands cost no DMA; without one, ``inner``."""
    def block(b, outer, inner, *fetch):
        return (_fetch_entry(fetch[0], heads, b, outer, inner, n_outer,
                             n_inner) if fetch else inner)
    return block


def _tile_runs(fetch_ref, heads: int, causal: bool, i, j, block_q: int,
               block_k: int):
    """Does this grid step's tile hold any work? Without a table the
    causal test: a kv block strictly in the future of every query row
    of the q block contributes nothing. With one, what it says of the
    step (whose innermost grid axis is the table's last)."""
    if fetch_ref is None:
        return (not causal) or (j * block_k <= i * block_q + (block_q - 1))
    inner = pl.program_id(2)
    return _fetch_entry(fetch_ref, heads, pl.program_id(0),
                        pl.program_id(1), inner, pl.num_programs(1),
                        pl.num_programs(2)) == inner


# ---------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------

def _fwd_kernel(fetch_ref, q_ref, k_ref, v_ref, segq_ref, segkv_ref,
                o_ref, lse_ref,
                acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                heads: int, window: int | None = None):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_tile_runs(fetch_ref, heads, causal, i, j, block_q, block_k))
    def _step():
        q = q_ref[0]                     # (bq, D)
        k = k_ref[0]                     # (bk, D)
        v = v_ref[0]                     # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)

        mask = None
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = rows >= cols
            if window is not None:
                mask = mask & (rows - cols < window)
        if segq_ref is not None:
            # segment blocks are (1, 8, b*): sublane-padded, row 0 live
            seg = segq_ref[0, 0][:, None] == segkv_ref[0, 0][None, :]
            mask = seg if mask is None else mask & seg
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0][:, None]                    # (bq, 1)
        l_prev = l_ref[:, 0][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # explicit zeroing: on a fully-masked block exp(NEG_INF - m_new)
        # underflows to 0 only when m_new is sane; when every block so
        # far was masked m_new == NEG_INF and exp(0) = 1 would leak
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, 0][:, None]
        safe_l = jnp.where(l == 0.0, 1.0, l)             # fully-masked rows
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        m = m_ref[:, 0]
        lse = jnp.where(l[:, 0] == 0.0, NEG_INF, m + jnp.log(l[:, 0]))
        # lse block is (1, 8, bq): 8 replicated sublanes to satisfy the
        # TPU (8, 128) tiling floor; row 0 is read back
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, segq, segkv, causal, block_q, block_k, group,
           interpret):
    out, _ = _flash_call(q, k, v, segq, segkv, causal, block_q, block_k,
                         group, interpret)
    return out


def _bind(kernel, name: str, n_inputs: int, segmented: bool,
          fetched: bool | None = None, **static):
    """``kernel(fetch, *inputs, segq, segkv, *outputs_and_scratch)`` as
    the function pallas calls: with segment ids it is handed exactly
    those refs, without them neither the table nor the two id blocks,
    and with a table but no ids (``fetched``: a window's forward) the
    table alone.
    ``name`` is the lowered custom call's ``kernel_name``; the callers
    pass the names these calls have always lowered under, so a program
    without segment ids keeps its text (and its compile-cache key)."""
    fetched = segmented if fetched is None else fetched

    def bound(*refs):
        if fetched and not segmented:
            refs = (*refs[:1 + n_inputs], None, None, *refs[1 + n_inputs:])
        elif not segmented:
            refs = (None, *refs[:n_inputs], None, None, *refs[n_inputs:])
        return kernel(*refs, **static)
    bound.__name__ = name
    return bound


def _tiled_call(kernel, fetch, args, *, interpret, out_shape,
                **grid_spec):
    """The ``pallas_call`` the three kernels share, made on ``args``.
    ``fetch`` (a ``_nearest_live`` table, or None) goes in flattened by
    scalar prefetch: the kernel's first ref and every index map's last
    argument."""
    if fetch is None:
        spec = pl.GridSpec(**grid_spec)
    else:
        spec = pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1,
                                            **grid_spec)
        args = [fetch.reshape(-1), *args]
    return pl.pallas_call(
        kernel,
        grid_spec=spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)


def _segment_operands(segq, segkv, block_q, block_k, causal):
    """What a call with segment ids adds: the ids sublane-padded
    (B, T) -> (B, 8, T) for the (8, 128) tiling floor, and the
    (B, nq, nk) liveness of its tiles."""
    B, T = segq.shape
    return (jnp.broadcast_to(segq[:, None, :], (B, 8, T)),
            jnp.broadcast_to(segkv[:, None, :], (B, 8, T)),
            live_tiles(segq, segkv, block_q, block_k, causal))


def _flash_call(q, k, v, segq, segkv, causal, block_q, block_k, group,
                interpret, window=None):
    """q: (B, KVH*G, T, D); k/v: (B, KVH, T, D);
    segq/segkv: (B, T) int32 or None; ``window`` (causal, no segment
    ids) the keys a query reads, its tiles' table made from it.
    Returns (out, lse)."""
    B, Hq, T, D = q.shape
    KVH = k.shape[1]
    scale = D ** -0.5
    qf = q.reshape(B * Hq, T, D)
    kf = k.reshape(B * KVH, T, D)
    vf = v.reshape(B * KVH, T, D)
    nq, nk = T // block_q, T // block_k

    def q_map(b, i, j, *_):
        return (b, i, 0)

    kv_of = _inner_block(Hq, nq, nk)

    def kv_map(b, i, j, *fetch):
        return (b // group, kv_of(b, i, j, *fetch), 0)

    def segq_map(b, i, j, *_):
        return (b // Hq, 0, i)

    def segkv_map(b, i, j, *fetch):
        return (b // Hq, 0, kv_of(b, i, j, *fetch))

    in_specs = [
        pl.BlockSpec((1, block_q, D), q_map),
        pl.BlockSpec((1, block_k, D), kv_map),
        pl.BlockSpec((1, block_k, D), kv_map),
    ]
    args = [qf, kf, vf]
    fetch = None
    if segq is not None:
        segq8, segkv8, live = _segment_operands(segq, segkv, block_q,
                                                block_k, causal)
        fetch = _nearest_live(live, axis=2)
        in_specs += [pl.BlockSpec((1, 8, block_q), segq_map),
                     pl.BlockSpec((1, 8, block_k), segkv_map)]
        args += [segq8, segkv8]
    window_static = {}
    if window is not None:
        live = _window_tiles(nq, nk, block_q, block_k, window)
        fetch = _nearest_live(jnp.broadcast_to(live, (B, nq, nk)), axis=2)
        window_static = {"fetched": True, "window": window}

    out, lse = _tiled_call(
        _bind(_fwd_kernel, "kernel", 3, segq is not None, scale=scale,
              causal=causal, block_q=block_q, block_k=block_k, heads=Hq,
              **window_static),
        fetch, args,
        grid=(B * Hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j, *_: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, 8, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )
    return out.reshape(B, Hq, T, D), lse[:, 0, :].reshape(B, Hq, T)


def _flash_fwd_rule(q, k, v, segq, segkv, causal, block_q, block_k,
                    group, interpret):
    out, lse = _flash_call(q, k, v, segq, segkv, causal, block_q,
                           block_k, group, interpret)
    return out, (q, k, v, segq, segkv, out, lse)


# Backward implementation selector. The hand-scheduled pallas backward
# gets the causal 2x by SKIPPING future blocks inside the kernel grid
# (pl.when, same trick as the forward) without leaving the MXU — the
# thing the triangular XLA scan couldn't do (see _flash_bwd_xla note).
BACKWARD_IMPL = "pallas"  # "pallas" | "xla"


def _flash_bwd_rule(causal, block_q, block_k, group, interpret, res, do):
    if BACKWARD_IMPL == "pallas":
        return _flash_bwd_pallas(causal, block_q, block_k, group,
                                 interpret, res, do)
    return _flash_bwd_xla(causal, block_q, block_k, group, interpret,
                          res, do)


# ---------------------------------------------------------------------
# pallas backward: dq kernel + dk/dv kernel
# ---------------------------------------------------------------------

def _bwd_mask(i, j, block_q, block_k, causal, segq_ref, segkv_ref):
    mask = None
    if causal:
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = rows >= cols
    if segq_ref is not None:
        seg = segq_ref[0, 0][:, None] == segkv_ref[0, 0][None, :]
        mask = seg if mask is None else mask & seg
    return mask


def _dq_kernel(fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               segq_ref, segkv_ref, dq_ref, dq_acc,
               *, scale, causal, block_q, block_k, heads):
    i = pl.program_id(1)   # q block (parallel)
    j = pl.program_id(2)   # kv block (arbitrary, accumulated)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_tile_runs(fetch_ref, heads, causal, i, j, block_q, block_k))
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]                     # (bq, 1)
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        p = jnp.exp(s - lse)
        mask = _bwd_mask(i, j, block_q, block_k, causal, segq_ref,
                         segkv_ref)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(fetch_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                segq_ref, segkv_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, block_q, block_k, heads):
    j = pl.program_id(1)   # kv block (parallel)
    i = pl.program_id(2)   # q block (arbitrary, accumulated)
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_runs(fetch_ref, heads, causal, i, j, block_q, block_k))
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        p = jnp.exp(s - lse)
        mask = _bwd_mask(i, j, block_q, block_k, causal, segq_ref,
                         segkv_ref)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dv += P^T dO ; dk += dS^T q
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_pallas(causal, block_q, block_k, group, interpret, res,
                      do):
    """Hand-scheduled backward: two pallas kernels sharing the forward's
    layout tricks (GQA via kv index-map division, sublane-padded
    residuals, tile skipping). dq runs on a (BH, nq, nk) grid with kv
    innermost; dk/dv on (BH, nk, nq) with q innermost, each
    accumulating its output block in VMEM across the arbitrary dim —
    skipped tiles never issue their matmuls nor their DMAs, which is
    the causal 2x the rectangular XLA scan left on the table and,
    with segment ids, the documents' share of the triangle."""
    q, k, v, segq, segkv, out, lse = res
    B, Hq, T, D = q.shape
    KVH = k.shape[1]
    scale = D ** -0.5

    qf = q.reshape(B * Hq, T, D)
    kf = k.reshape(B * KVH, T, D)
    vf = v.reshape(B * KVH, T, D)
    dof = do.reshape(B * Hq, T, D)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * Hq, T)              # (BH, T)
    lsef = lse.reshape(B * Hq, T)
    # sublane-pad the per-row residuals to the (8, 128) tiling floor,
    # exactly as the forward stores lse
    lse8 = jnp.broadcast_to(lsef[:, None, :], (B * Hq, 8, T))
    delta8 = jnp.broadcast_to(delta[:, None, :], (B * Hq, 8, T))

    nq, nk = T // block_q, T // block_k
    args = [qf, kf, vf, dof, lse8, delta8]
    has_seg = segq is not None
    kv_fetch = q_fetch = None
    if has_seg:
        segq8, segkv8, live = _segment_operands(segq, segkv, block_q,
                                                block_k, causal)
        args += [segq8, segkv8]
        kv_fetch = _nearest_live(live, axis=2)               # (B, nq, nk)
        q_fetch = _nearest_live(jnp.swapaxes(live, 1, 2), axis=2)

    def specs(i_of, j_of):
        """The eight operands' specs from a grid step's q block and kv
        block (each a function of the step and, with segment ids, the
        prefetched table)."""
        def q_map(*g):
            return (g[0], i_of(*g), 0)

        def kv_map(*g):
            return (g[0] // group, j_of(*g), 0)

        def row_map(*g):
            return (g[0], 0, i_of(*g))

        def segq_map(*g):
            return (g[0] // Hq, 0, i_of(*g))

        def segkv_map(*g):
            return (g[0] // Hq, 0, j_of(*g))

        in_specs = [
            pl.BlockSpec((1, block_q, D), q_map),    # q
            pl.BlockSpec((1, block_k, D), kv_map),   # k
            pl.BlockSpec((1, block_k, D), kv_map),   # v
            pl.BlockSpec((1, block_q, D), q_map),    # do
            pl.BlockSpec((1, 8, block_q), row_map),  # lse
            pl.BlockSpec((1, 8, block_q), row_map),  # delta
        ]
        if has_seg:
            in_specs += [pl.BlockSpec((1, 8, block_q), segq_map),
                         pl.BlockSpec((1, 8, block_k), segkv_map)]
        return in_specs

    def outer(b, outer, inner, *_):
        return outer

    static = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, heads=Hq)

    dq = _tiled_call(
        _bind(_dq_kernel, "f", 6, has_seg, **static),
        kv_fetch, args,
        grid=(B * Hq, nq, nk),
        in_specs=specs(outer, _inner_block(Hq, nq, nk)),
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda b, i, j, *_: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )

    # dk/dv per Q-HEAD (B*Hq) — grouped heads fold onto their shared kv
    # head afterwards, so no two grid rows write the same output block.
    # The grid is (b, j, i): the same maps with the roles swapped
    dk_h, dv_h = _tiled_call(
        _bind(_dkv_kernel, "f", 6, has_seg, **static),
        q_fetch, args,
        grid=(B * Hq, nk, nq),
        in_specs=specs(_inner_block(Hq, nk, nq), outer),
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i, *_: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i, *_: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hq, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )

    dq = dq.reshape(B, Hq, T, D)
    dk = dk_h.reshape(B, KVH, group, T, D).sum(axis=2)
    dv = dv_h.reshape(B, KVH, group, T, D).sum(axis=2)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


def _flash_bwd_xla(causal, block_q, block_k, group, interpret, res, do):
    """Blockwise backward from lse residuals — O(T·block) memory.

    dS = P ∘ (dP − δ) with P = exp(S − lse), dP = dO·Vᵀ,
    δ = rowsum(dO ∘ O); dQ = dS·K, dK = dSᵀ·Q, dV = Pᵀ·dO.

    Deliberately a RECTANGULAR scan over kv blocks (each step contracts
    the full (T × blk) panel) even though causal masking wastes ~half
    its FLOPs on future blocks. The "obvious" fix — a triangular
    (q-tile × kv-tile) scan visiting only qb ≥ jb pairs — was measured
    SLOWER on the v5e bench (36.7% vs 42.7% MFU end-to-end): it
    serializes nb(nb+1)/2 small matmuls and adds read-modify-write
    accumulator traffic, losing more to MXU underutilization than the
    skipped FLOPs save. Kept as the fallback/reference implementation
    behind ``BACKWARD_IMPL``; the pallas kernels above get the causal
    2x properly (block skipping inside the grid).
    """
    q, k, v, segq, segkv, out, lse = res
    B, Hq, T, D = q.shape
    KVH = k.shape[1]
    scale = D ** -0.5
    kr = jnp.repeat(k, group, axis=1)          # (B, Hq, T, D) — see note
    vr = jnp.repeat(v, group, axis=1)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (B, Hq, T)

    nk = T // block_k
    rows = jnp.arange(T)

    def kv_block(carry, jb):
        dq_acc, dk_acc, dv_acc = carry
        k0 = jb * block_k
        ks = jax.lax.dynamic_slice_in_dim(kr, k0, block_k, 2)
        vs = jax.lax.dynamic_slice_in_dim(vr, k0, block_k, 2)
        cols = k0 + jnp.arange(block_k)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, ks,
                       preferred_element_type=jnp.float32) * scale
        mask = None
        if causal:
            mask = (rows[:, None] >= cols[None, :])[None, None]
        if segq is not None:
            sk = jax.lax.dynamic_slice_in_dim(segkv, k0, block_k, 1)
            seg = (segq[:, :, None] == sk[:, None, :])[:, None]
            mask = seg if mask is None else mask & seg
        p = jnp.exp(s - lse[..., None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vs.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                     ks.astype(jnp.float32))
        dk_b = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        dv_b = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dk_acc = jax.lax.dynamic_update_slice_in_dim(dk_acc, dk_b, k0, 2)
        dv_acc = jax.lax.dynamic_update_slice_in_dim(dv_acc, dv_b, k0, 2)
        return (dq_acc, dk_acc, dv_acc), None

    zeros_q = jnp.zeros((B, Hq, T, D), jnp.float32)
    (dq, dk_full, dv_full), _ = jax.lax.scan(
        kv_block, (zeros_q, zeros_q, zeros_q), jnp.arange(nk))

    # fold grouped-query heads back onto their shared kv head
    dk = dk_full.reshape(B, KVH, group, T, D).sum(axis=2)
    dv = dv_full.reshape(B, KVH, group, T, D).sum(axis=2)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------
# public wrapper
# ---------------------------------------------------------------------

def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids_q: jax.Array | None = None,
    segment_ids_kv: jax.Array | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Flash attention with the ``dot_product_attention`` layout:
    q (B, T, H, D); k, v (B, T, KVH, D) → (B, T, H, D).

    Causality is over local indices; combined with segment ids this is
    exact for packed documents (module docstring). ``window`` (causal,
    no segment ids) keeps for query ``p`` the keys ``p - window + 1 ..
    p``: a tile wholly before every query's window is one more dead
    tile of the table the kernel is handed (``_window_tiles``). The
    windowed call is the forward alone (serving's prefill): it has no
    gradient rule. ``block_q`` /
    ``block_k`` default as ``tile_for`` says, by whether the call
    carries segment ids. ``interpret=None``
    selects the pallas interpreter when the operands' computation is
    not for a TPU (``ops.attention.computation_devices``), so tests run
    on CPU; the interpreter is refused for a TPU computation.
    """
    B, T, H, D = q.shape
    KVH = k.shape[2]
    assert H % KVH == 0
    group = H // KVH
    block_q, block_k = tile_for(T, segment_ids_q is not None, block_q,
                                block_k)
    if not block_q or not block_k:
        raise ValueError(
            f"T={T} has no 128-multiple block divisor; use the XLA path")
    from kubeflow_rm_tpu.ops.attention import computation_devices
    on_tpu = computation_devices(q)[0] == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "interpret=True on a TPU computation: the pallas interpreter "
            "is the CPU test path, the chip runs the compiled kernel")

    qh = jnp.swapaxes(q, 1, 2)   # (B, H, T, D)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    segq = None if segment_ids_q is None else segment_ids_q.astype(
        jnp.int32)
    segkv = None if segment_ids_kv is None else segment_ids_kv.astype(
        jnp.int32)
    if window is not None:
        if not causal or segq is not None:
            raise ValueError("a window is causal and takes no segment ids")
        out, _lse = _flash_call(qh, kh, vh, None, None, True, block_q,
                                block_k, group, interpret, window=window)
        return jnp.swapaxes(out, 1, 2)
    out = _flash(qh, kh, vh, segq, segkv, causal, block_q, block_k,
                 group, interpret)
    return jnp.swapaxes(out, 1, 2)
