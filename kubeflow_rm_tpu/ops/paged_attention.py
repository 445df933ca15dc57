"""Decode attention over a block-paged KV pool, read through the block table.

One token a slot attends over that slot's logical strip — the blocks
its row of the block table names, in order — without the strip ever
being built for more than one layer. Two implementations of one
function, chosen the way ``ops/attention.py`` chooses flash (by what
``computation_devices`` observes; no flag, no environment variable):

- **pallas TPU kernel** on a one-device TPU program. The pool stays in
  HBM; block tables, lengths and the layer index are scalar-prefetched;
  the kernel walks the live slots' blocks in chunks and copies only
  ``block_tables[slot, : ceil(len / BS)]`` of layer ``layer`` into a
  double-buffered VMEM scratch, one DMA a block (a block of one layer
  is contiguous: ``(BS, KVH, hd)``, 32 KB at Mistral's widths). An
  inactive slot, or one with nothing cached yet, costs no DMA. Online
  softmax in float32 over bf16 scores accumulated in float32,
  probabilities cast to the value dtype before the second matmul: the
  precision of ``dot_product_attention``'s XLA path, nothing lower.
- **plain XLA** elsewhere (CPU tests, any multi-device mesh): this
  layer's ``pool[layer, block_tables]`` gathered to ``(B, S, KVH,
  hd)``, the new column written at its offset, and
  ``dot_product_attention`` under the position mask — exactly what
  the whole-cache gather computed, a layer at a time.

**How the kernel handles GQA without touching the pool's layout.** A
block's rows are ``(token, kv head)`` pairs — ``(BS * KVH, hd)`` — so
all ``H`` query heads are multiplied against every row and a constant
bias keeps, for query head ``h``, only the rows of kv head ``h // G``
(a block-diagonal score matrix). The MXU does ``KVH`` times the
necessary multiplies on a matrix whose cost is loading K either way;
in exchange a block is one DMA, one ``(128, 128)`` tile at Mistral's
widths, and the decode step's one-column scatter stays one whole tile.

**The mask.** A key is seen iff its position is filled and not after
the query's. The XLA path reads that from the pool's positions. The
kernel reads it from ``lengths``: in a slot's strip the token at
offset ``t`` has position ``t`` (prefill right-pads, decode appends),
so the filled positions not after the query are the offsets below the
slot's write index; whatever a recycled block, a NULL table entry or
a shared block's tail holds past it is never copied or is masked by
its column index. This token's own K/V enters as the online softmax's
starting state, so the pool is only read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_rm_tpu.ops.attention import (
    NEG_INF, _log_choice, computation_devices, dot_product_attention,
)

#: tokens of one slot the kernel copies and multiplies at a time (a
#: chunk is ``CHUNK_TOKENS // block_size`` blocks, two chunks in
#: flight). Measured on the v5e at Mistral's widths, a layer's call in
#: us at 64 / 128 / 256 / 512: nine slots of 40-400 tokens 24.3 / 23.4
#: / 22.5 / 28.6, sixteen full 2048-token strips 336 / 231 / 186 / 186
#: (PERF.md section 6, PR 31).
CHUNK_TOKENS = 256


def kernel_eligible(q, pool_k) -> bool:
    """Do the shapes tile the chip? The head dimension fills the 128
    lanes, a block's ``BS * KVH`` rows and the ``H`` query heads whole
    sublane tiles of their (packed) dtype."""
    _, H, hd = q.shape
    _, _, BS, KVH, _ = pool_k.shape
    sublanes = 8 * (4 // jnp.dtype(pool_k.dtype).itemsize)
    return (hd % 128 == 0 and (BS * KVH) % sublanes == 0
            and H % sublanes == 0 and H % KVH == 0)


def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, layer,
                           block_tables, lengths, active, *,
                           positions_q, kv_positions,
                           impl: str = "auto"):
    """Attention of one new token a slot over its paged strip.

    Args:
      q: (B, H, hd) the token's queries, a slot each.
      k_new, v_new: (B, KVH, hd) its key and value: the strip's column
        at offset ``lengths``, not yet in the pool.
      pool_k, pool_v: (L, NB, BS, KVH, hd) the block pool, all layers.
      layer: () int32, the layer to read.
      block_tables: (B, MAXB) int32; a row's blocks in strip order.
      lengths: (B,) int32 tokens the slot's strip already holds, which
        is the offset this token lands at.
      active: (B,) bool; an inactive row's output is unspecified.
      positions_q: (B,) int32 the token's position (``_UNFILLED`` for
        an inactive row); kv_positions: (B, MAXB * BS) int32 the
        strips' positions with this token's written in. Both are the
        XLA path's mask; the kernel masks by ``lengths`` (module
        docstring).
      impl: "auto" (the kernel on a one-device TPU program whose
        shapes tile, else XLA), "pallas" (force the kernel;
        interpreter off-TPU) or "xla".

    Returns (B, H, hd) in q.dtype.
    """
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    platform, n_devices = computation_devices(q)
    use_kernel = impl == "pallas"
    if impl == "auto":
        use_kernel = (platform == "tpu" and n_devices == 1
                      and kernel_eligible(q, pool_k))
        if not use_kernel:
            _log_choice("paged_decode_xla", False, platform, n_devices,
                        "not a one-device tpu program whose shapes tile")
    if use_kernel:
        interpret = platform != "tpu"
        _log_choice("paged_decode", interpret, platform, n_devices,
                    f"impl={impl}")
        return _paged_decode_kernel(
            q, k_new, v_new, pool_k, pool_v, layer, block_tables,
            jnp.where(active, lengths, 0), interpret=interpret)

    B, MAXB = block_tables.shape
    BS = pool_k.shape[2]
    rows = jnp.arange(B, dtype=jnp.int32)

    def strip(pool, new):
        g = pool[layer, block_tables].reshape(B, MAXB * BS,
                                              *pool.shape[3:])
        return g.at[rows, lengths].set(new)

    out = dot_product_attention(
        q[:, None], strip(pool_k, k_new), strip(pool_v, v_new),
        causal=True, positions_q=positions_q[:, None],
        positions_kv=kv_positions)
    return out[:, 0]


def _kernel(layer_ref, lengths_ref, tables_ref,        # scalar prefetch
            q_ref, kn_ref, vn_ref, head_bias_ref, col_tok_ref,
            k_hbm, v_hbm,
            o_ref,
            item_slot, item_chunk, kbuf, vbuf, ksem, vsem,
            *, block_size: int, chunk_blocks: int):
    B, H, _ = q_ref.shape
    BS, CB = block_size, chunk_blocks
    layer = layer_ref[0]

    def n_blocks(b):
        return (lengths_ref[b] + BS - 1) // BS

    # the work list: one item a chunk of a live slot's blocks, slots
    # in order — so the copies of one slot's first chunk run under the
    # last slot's arithmetic, and an empty slot is never visited
    def list_slot(b, n):
        def put(c, n):
            item_slot[n] = b
            item_chunk[n] = c
            return n + 1
        return jax.lax.fori_loop(0, (n_blocks(b) + CB - 1) // CB, put, n)

    n_items = jax.lax.fori_loop(0, B, list_slot, 0)

    def copies(i, buf):
        """Item ``i``'s block copies into buffer ``buf``, each under
        the condition it is issued and waited for."""
        b, c = item_slot[i], item_chunk[i]
        nb = n_blocks(b)
        out = []
        for j in range(CB):
            blk = tables_ref[b, jnp.minimum(c * CB + j,
                                            tables_ref.shape[1] - 1)]
            out.append((c * CB + j < nb, (
                pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      kbuf.at[buf, j], ksem.at[buf]),
                pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[buf, j], vsem.at[buf]))))
        return out

    def start(i, buf):
        for live, pair in copies(i, buf):
            @pl.when(live)
            def _():
                for cp in pair:
                    cp.start()

    def wait(i, buf):
        for live, pair in copies(i, buf):
            @pl.when(live)
            def _():
                for cp in pair:
                    cp.wait()

    # a chunk's unfilled tail is never copied: its columns are masked,
    # and 0 x (whatever VMEM held) must still be 0 in the second matmul
    vbuf[...] = jnp.zeros_like(vbuf)
    # a slot with nothing cached attends to its own token alone
    o_ref[...] = vn_ref[...]

    @pl.when(n_items > 0)
    def _():
        start(0, 0)

    head_bias = head_bias_ref[...]                     # (H, CB*R) f32
    col_tok = col_tok_ref[...]                         # (1, CB*R) i32

    def body(i, carry):
        b, c = item_slot[i], item_chunk[i]
        buf = i % 2

        @pl.when(i + 1 < n_items)
        def _():
            start(i + 1, 1 - buf)

        q = q_ref[b]                                   # (H, hd), scaled
        # the slot's own token starts the online softmax: m = its
        # score, l = 1, acc = its value
        s_self = jnp.sum(q.astype(jnp.float32)
                         * kn_ref[b].astype(jnp.float32),
                         axis=1, keepdims=True)        # (H, 1)
        first = c == 0
        m_prev = jnp.where(first, s_self, carry[0])
        l_prev = jnp.where(first, 1.0, carry[1])
        acc_prev = jnp.where(first, vn_ref[b].astype(jnp.float32),
                             carry[2])

        wait(i, buf)
        k = kbuf[buf].reshape(-1, kbuf.shape[-1])      # (CB*R, hd)
        v = vbuf[buf].reshape(-1, vbuf.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (H, CB*R)
        s = s + head_bias
        s = jnp.where(col_tok < lengths_ref[b] - c * (CB * BS), s,
                      NEG_INF)
        m = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m)
        p = jnp.exp(s - m)
        l = alpha * l_prev + p.sum(axis=1, keepdims=True)
        acc = alpha * acc_prev + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        # every chunk leaves the slot's answer so far; the last stays
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return m, l, acc

    jax.lax.fori_loop(
        0, n_items, body,
        (jnp.zeros((H, 1), jnp.float32), jnp.ones((H, 1), jnp.float32),
         jnp.zeros(o_ref.shape[1:], jnp.float32)))


def _paged_decode_kernel(q, k_new, v_new, pool_k, pool_v, layer,
                         block_tables, lengths, *, interpret: bool):
    B, H, hd = q.shape
    L, NB, BS, KVH, _ = pool_k.shape
    G = H // KVH
    MAXB = block_tables.shape[1]
    CB = max(1, min(CHUNK_TOKENS // BS, MAXB))
    R = BS * KVH                                       # rows a block

    # the rounding dot_product_attention gives the scaled query
    q = q * hd ** -0.5
    # a kv head's column under each of its G query heads
    kn = jnp.repeat(k_new, G, axis=1)                  # (B, H, hd)
    vn = jnp.repeat(v_new, G, axis=1)
    # a chunk's columns are (block, token, kv head): query head h
    # keeps kv head h // G; col_tok is the column's token in the chunk
    col = np.arange(CB * R)
    head_bias = np.where(
        (np.arange(H)[:, None] // G) == (col % KVH)[None, :],
        0.0, NEG_INF).astype(np.float32)
    col_tok = (col // KVH).astype(np.int32)[None, :]

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, block_size=BS, chunk_blocks=CB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[full(B, H, hd), full(B, H, hd), full(B, H, hd),
                      full(H, CB * R), full(1, CB * R), hbm, hbm],
            out_specs=full(B, H, hd),
            scratch_shapes=[
                pltpu.SMEM((B * pl.cdiv(MAXB, CB),), jnp.int32),
                pltpu.SMEM((B * pl.cdiv(MAXB, CB),), jnp.int32),
                pltpu.VMEM((2, CB, R, hd), pool_k.dtype),
                pltpu.VMEM((2, CB, R, hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      q, kn, vn, head_bias, col_tok,
      pool_k.reshape(L, NB, R, hd), pool_v.reshape(L, NB, R, hd))
