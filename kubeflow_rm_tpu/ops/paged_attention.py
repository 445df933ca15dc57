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
  double-buffered VMEM scratch (a block of one layer is contiguous:
  ``(BS, KVH, hd)``, 32 KB at Mistral's widths). A chunk whose blocks
  are all live and whose ids run consecutively is one region of the
  pool and goes in one DMA for K and one for V; any other chunk goes a
  DMA a live block (``whole_chunks``). An inactive slot, or one with
  nothing cached yet, costs no DMA. Online
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
A head narrower than the lanes whose token's heads fill them (LFM2's 8
x 64) is kept lane-dense (``lane_dense``): a block is ``(BS, KVH *
hd)``, rows tokens and lanes (kv head, dim). There the query is spread
outside the kernel to ``(H, KVH * hd)``, head ``h`` in the lanes of kv
head ``h // G`` and zeros in the others, so ``q @ K^T`` is each head's
own scores with no bias, and ``p @ V`` leaves head ``h``'s output in
those lanes, picked out after the call.

**The mask.** A key is seen iff its position is filled and not after
the query's. The XLA path reads that from the pool's positions. The
kernel reads it from ``lengths``: in a slot's strip the token at
offset ``t`` has position ``t`` (prefill right-pads, decode appends),
so the filled positions not after the query are the offsets below the
slot's write index; whatever a recycled block, a NULL table entry or
a shared block's tail holds past it is never copied or is masked by
its column index. This token's own K/V enters as the online softmax's
starting state, so the pool is only read.

**A window layer** (``window_decode_attention``) keeps no block table:
a slot's state is a ring of its last ``Wc`` positions (``Wc`` the
window rounded up to whole blocks), position ``p`` at offset ``p %
Wc``, overwritten in place as the context grows. The same kernel
reads it (the ring laid out as blocks a slot owns in order, a block
the kernel's chunk) and never more than its ``Wc`` positions, however
long the context; a key is seen iff it is filled and among the
``window`` newest, the token itself one of them. Its mask is the age
of a column, ``(n - 1 - offset) mod Wc`` for a slot holding ``n``
tokens, against ``min(window - 1, n)``: two scalars a slot the kernel
is handed beside the lengths. The XLA path gathers nothing: the ring
is the strip, its positions worked out from ``n``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_rm_tpu.ops.attention import (
    NEG_INF, _log_choice, computation_devices, dot_product_attention,
)

#: a position no query reaches (``models.decode._UNFILLED``)
_UNFILLED = np.iinfo(np.int32).max

#: tokens of one slot the kernel copies and multiplies at a time (a
#: chunk is ``CHUNK_TOKENS // block_size`` blocks, two chunks in
#: flight). Measured on the v5e at Mistral's widths, a layer's call in
#: us at 64 / 128 / 256 / 512: nine slots of 40-400 tokens 24.3 / 23.4
#: / 22.5 / 28.6, sixteen full 2048-token strips 336 / 231 / 186 / 186
#: (PERF.md section 6, PR 31).
CHUNK_TOKENS = 256


def chunk_blocks(block_size: int, max_blocks: int) -> int:
    """Blocks of one slot's strip the kernel copies and multiplies at a
    time: ``CHUNK_TOKENS`` worth, no more than a strip holds."""
    return max(1, min(CHUNK_TOKENS // block_size, max_blocks))


def whole_chunks(blocks, cb: int) -> np.ndarray:
    """For a strip's blocks in table order, the number of whole chunks
    among its first ``k`` chunks, ``k = 0 .. len(blocks) // cb``: a
    chunk of ``cb`` blocks is whole where its ids run consecutively,
    so that the kernel copies it as one region of the pool. A slot
    whose cached tokens fill ``n`` blocks walks ``ceil(n / cb)`` chunks,
    ``whole_chunks(blocks, cb)[n // cb]`` of them whole."""
    ids = np.asarray(blocks, np.int64)
    runs = ids[:len(ids) // cb * cb].reshape(-1, cb)
    whole = (np.diff(runs, axis=1) == 1).all(axis=1)
    return np.concatenate([[0], np.cumsum(whole)])


def lane_dense(kv_heads: int, head_dim: int) -> bool:
    """Does a block pool keep a token's heads as one row of ``kv_heads
    * head_dim`` lanes, ``(L, NB, BS, KVH * hd)``? Where a head is
    narrower than the 128 lanes but a token's heads fill them whole: a
    5-D pool of such a head (LFM2's 8 x 64) the chip keeps with the
    block index minor, to spare padding 64 lanes to 128, so that no
    block of it is contiguous and every reader relays the whole pool
    first; a block ``(BS, KVH * hd)`` is one region, unpadded."""
    return head_dim % 128 != 0 and kv_heads * head_dim % 128 == 0


def kernel_eligible(q, pool_k) -> bool:
    """Do the shapes tile the chip? The head dimension fills the 128
    lanes, a block's ``BS * KVH`` rows and the ``H`` query heads whole
    sublane tiles of their (packed) dtype; or, for a lane-dense pool
    (``lane_dense``), a token's heads fill the lanes and a block's
    ``BS`` rows whole sublane tiles."""
    _, H, hd = q.shape
    sublanes = 8 * (4 // jnp.dtype(pool_k.dtype).itemsize)
    if pool_k.ndim == 4:
        _, _, BS, lanes = pool_k.shape
        KVH = lanes // hd
        return (lanes == KVH * hd and lane_dense(KVH, hd)
                and BS % sublanes == 0 and H % sublanes == 0
                and H % KVH == 0)
    _, _, BS, KVH, _ = pool_k.shape
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
      pool_k, pool_v: (L, NB, BS, KVH, hd) the block pool, all layers,
        or (L, NB, BS, KVH * hd) where it is lane-dense
        (``lane_dense``).
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
                                              *new.shape[1:])
        return g.at[rows, lengths].set(new)

    out = dot_product_attention(
        q[:, None], strip(pool_k, k_new), strip(pool_v, v_new),
        causal=True, positions_q=positions_q[:, None],
        positions_kv=kv_positions)
    return out[:, 0]


def _kernel(layer_ref, lengths_ref, tables_ref,        # scalar prefetch
            *refs, block_size: int, chunk_blocks: int,
            ring: int | None = None, lanes: bool = False):
    if ring is None:
        ring_ref = None
    else:                   # a window's ring: one more scalar prefetch
        ring_ref, *refs = refs
    if lanes:               # a lane-dense pool's scores need no bias
        refs = (*refs[:3], None, *refs[3:])
    (q_ref, kn_ref, vn_ref, head_bias_ref, col_tok_ref, k_hbm, v_hbm,
     o_ref, item_slot, item_chunk, kbuf, vbuf, ksem, vsem,
     *more) = refs
    B, H, _ = q_ref.shape
    BS, CB = block_size, chunk_blocks
    # where CB > 1: a whole chunk's first block an item, or -1
    item_first = more[0] if more else None
    MAXB = tables_ref.shape[1]
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

    def chunk_ids(i):
        """Item ``i``'s chunk, its slot's live blocks and the chunk's
        ``CB`` table entries (clamped to the table)."""
        b, c = item_slot[i], item_chunk[i]
        return c, n_blocks(b), [
            tables_ref[b, jnp.minimum(c * CB + j, MAXB - 1)]
            for j in range(CB)]

    def per_block(c, nb, ids, buf, act):
        """A copy a live block into buffer ``buf``, each under the
        condition it is started and waited for."""
        for j, blk in enumerate(ids):
            @pl.when(c * CB + j < nb)
            def _():
                act(pltpu.make_async_copy(k_hbm.at[layer, blk],
                                          kbuf.at[buf, j], ksem.at[buf]))
                act(pltpu.make_async_copy(v_hbm.at[layer, blk],
                                          vbuf.at[buf, j], vsem.at[buf]))

    def whole_chunk(first, buf, act):
        """``CB`` blocks from ``first`` on in one copy for K and one
        for V: the bytes the block copies would put at the same
        offsets."""
        act(pltpu.make_async_copy(k_hbm.at[layer, pl.ds(first, CB)],
                                  kbuf.at[buf], ksem.at[buf]))
        act(pltpu.make_async_copy(v_hbm.at[layer, pl.ds(first, CB)],
                                  vbuf.at[buf], vsem.at[buf]))

    def start(i, buf):
        c, nb, ids = chunk_ids(i)
        if CB == 1:
            return per_block(c, nb, ids, buf, lambda cp: cp.start())
        # whole: every block live and the ids consecutive, decided once
        # an item here and kept for its wait
        whole = (c + 1) * CB <= nb
        for j in range(1, CB):
            whole &= ids[j] == ids[0] + j
        item_first[i] = jnp.where(whole, ids[0], -1)
        pl.when(whole)(lambda: whole_chunk(ids[0], buf,
                                           lambda cp: cp.start()))
        pl.when(jnp.logical_not(whole))(lambda: per_block(
            c, nb, ids, buf, lambda cp: cp.start()))

    def wait(i, buf):
        if CB == 1:
            return per_block(*chunk_ids(i), buf, lambda cp: cp.wait())
        first = item_first[i]
        pl.when(first >= 0)(lambda: whole_chunk(first, buf,
                                                lambda cp: cp.wait()))
        pl.when(first < 0)(lambda: per_block(*chunk_ids(i), buf,
                                             lambda cp: cp.wait()))

    # a chunk's unfilled tail is never copied: its columns are masked,
    # and 0 x (whatever VMEM held) must still be 0 in the second matmul
    vbuf[...] = jnp.zeros_like(vbuf)
    # a slot with nothing cached attends to its own token alone
    o_ref[...] = jnp.broadcast_to(vn_ref[...], o_ref.shape)

    @pl.when(n_items > 0)
    def _():
        start(0, 0)

    if head_bias_ref is not None:
        head_bias = head_bias_ref[...]                 # (H, CB*R) f32
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
        if head_bias_ref is not None:
            s = s + head_bias
        if ring_ref is None:
            s = jnp.where(col_tok < lengths_ref[b] - c * (CB * BS), s,
                          NEG_INF)
        else:
            # a column's age: how many tokens before the slot's newest
            # cached one it was written, read round the ring
            newest, seen = ring_ref[0, b], ring_ref[1, b]
            off = c * (CB * BS) + col_tok
            age = jnp.where(off <= newest, newest - off,
                            newest - off + ring)
            s = jnp.where(age < seen, s, NEG_INF)
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
                         block_tables, lengths, *, interpret: bool,
                         ring=None):
    """The kernel's call. ``ring`` = (``Wc``, (2, B) int32: each slot's
    newest cached offset and the ages it sees) for a window's ring,
    whose ``lengths`` are then its filled offsets."""
    B, H, hd = q.shape
    KVH = k_new.shape[1]
    L, NB, BS = pool_k.shape[:3]
    G = H // KVH
    MAXB = block_tables.shape[1]
    CB = chunk_blocks(BS, MAXB)
    lanes = pool_k.ndim == 4                           # lane_dense
    R = BS if lanes else BS * KVH                      # rows a block
    W = KVH * hd if lanes else hd                      # lanes a row
    items = B * pl.cdiv(MAXB, CB)                      # the most a call

    # the rounding dot_product_attention gives the scaled query
    q = q * hd ** -0.5
    if lanes:
        # a chunk's rows are tokens, its lanes (kv head, dim): query
        # head h holds its scaled query in the lanes of kv head h // G
        # and zeros in the others, so q @ K^T is each head's own scores
        # and p @ V holds its output in those lanes; the token's own
        # column, a row, is every head's
        own = (np.arange(H)[:, None] // G) == np.arange(KVH)[None, :]
        q = jnp.where(own[None, :, :, None], q[:, :, None, :],
                      0).reshape(B, H, W)
        kn = k_new.reshape(B, 1, W)
        vn = v_new.reshape(B, 1, W)
        bias = ()
        col_tok = np.arange(CB * R, dtype=np.int32)[None, :]
    else:
        # a kv head's column under each of its G query heads
        kn = jnp.repeat(k_new, G, axis=1)              # (B, H, hd)
        vn = jnp.repeat(v_new, G, axis=1)
        # a chunk's columns are (block, token, kv head): query head h
        # keeps kv head h // G; col_tok is the column's token in the
        # chunk
        col = np.arange(CB * R)
        bias = (np.where(
            (np.arange(H)[:, None] // G) == (col % KVH)[None, :],
            0.0, NEG_INF).astype(np.float32),)
        col_tok = (col // KVH).astype(np.int32)[None, :]

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    # a block's (BS, KVH, hd) lies in HBM as one (R, hd) tile when the
    # head fills one lane tile, so the pool goes in as (L, NB, R, hd),
    # a bitcast. A wider head lies in (KVH, 128) tiles, where that
    # reshape is a copy of the whole pool every step (537 MB at
    # Qwen3-Next's 2 x 256): the pool goes in as it lies, a block is
    # copied as it lies and flattened in VMEM. A lane-dense pool goes
    # in as it lies, a block (BS, KVH * hd)
    wide = hd > 128
    block = (BS, KVH, hd) if wide else (R, W)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    static, scalars, name = {}, (), "paged_decode_attention"
    if ring is not None:
        static = {"ring": ring[0]}
        scalars = (ring[1].astype(jnp.int32),)
        name = "window_decode_attention"
    if lanes:
        static["lanes"] = True
    out = pl.pallas_call(
        functools.partial(_kernel, block_size=BS, chunk_blocks=CB,
                          **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(scalars),
            grid=(1,),
            in_specs=[full(B, H, W), full(*kn.shape), full(*vn.shape),
                      *[full(H, CB * R) for _ in bias], full(1, CB * R),
                      hbm, hbm],
            out_specs=full(B, H, W),
            scratch_shapes=[
                pltpu.SMEM((items,), jnp.int32),
                pltpu.SMEM((items,), jnp.int32),
                pltpu.VMEM((2, CB, *block), pool_k.dtype),
                pltpu.VMEM((2, CB, *block), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                *([pltpu.SMEM((items,), jnp.int32)] if CB > 1 else []),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), block_tables.astype(jnp.int32), *scalars,
      q, kn, vn, *bias, col_tok,
      *((pool_k, pool_v) if wide or lanes else
        (pool_k.reshape(L, NB, R, hd), pool_v.reshape(L, NB, R, hd))))
    if lanes:                  # head h's output, its kv head's lanes
        heads = np.arange(H)
        out = out.reshape(B, H, KVH, hd)[:, heads, heads // G]
    return out


def ring_block(ring_len: int) -> int:
    """Positions of a window's ring the kernel copies at a time: one
    chunk, ``CHUNK_TOKENS``, where that divides the ring."""
    return math.gcd(ring_len, CHUNK_TOKENS)


def window_decode_attention(q, k_new, v_new, ring_k, ring_v, layer,
                            lengths, active, *, window: int,
                            impl: str = "auto"):
    """Attention of one new token a slot over the ``window`` newest
    positions of its window layer's ring.

    Args:
      q: (B, H, hd) the token's queries; k_new, v_new: (B, KVH, hd) its
        key and value, not yet in the ring.
      ring_k, ring_v: (Lw, B, Wc, KVH, hd) every window layer's ring,
        position ``p`` of slot ``b`` at ``[:, b, p % Wc]``; ``Wc >=
        window``.
      layer: () int32, the window layer to read.
      lengths: (B,) int32 tokens the slot holds before this one (its
        position); active: (B,) bool.
      window: the keys a query reads, itself among them.
      impl: as ``paged_decode_attention``'s.

    Returns (B, H, hd) in q.dtype; an inactive row's is unspecified.
    """
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    Lw, B, Wc, KVH, hd = ring_k.shape
    if Wc < window:
        raise ValueError(f"a ring of {Wc} cannot hold a window of {window}")
    n = jnp.where(active, lengths, 0).astype(jnp.int32)
    BS = ring_block(Wc)
    nb = Wc // BS
    pool_k = ring_k.reshape(Lw, B * nb, BS, KVH, hd)
    platform, n_devices = computation_devices(q)
    use_kernel = impl == "pallas"
    if impl == "auto":
        use_kernel = (platform == "tpu" and n_devices == 1
                      and kernel_eligible(q, pool_k))
        if not use_kernel:
            _log_choice("window_decode_xla", False, platform, n_devices,
                        "not a one-device tpu program whose shapes tile")
    if use_kernel:
        interpret = platform != "tpu"
        _log_choice("window_decode", interpret, platform, n_devices,
                    f"impl={impl}")
        tables = (jnp.arange(B, dtype=jnp.int32)[:, None] * nb
                  + jnp.arange(nb, dtype=jnp.int32)[None, :])
        ages = jnp.stack([jnp.remainder(n - 1, Wc),
                          jnp.minimum(window - 1, n)])
        return _paged_decode_kernel(
            q, k_new, v_new, pool_k, ring_v.reshape(pool_k.shape), layer,
            tables, jnp.minimum(n, Wc), interpret=interpret,
            ring=(Wc, ages))

    # the ring with this token written in: offset o then holds the
    # position ``n - age``, age = (n - o) mod Wc
    rows = jnp.arange(B, dtype=jnp.int32)
    at = jnp.remainder(n, Wc)
    age = jnp.remainder(n[:, None] - jnp.arange(Wc, dtype=jnp.int32), Wc)
    kv_positions = jnp.where((age < window) & (age <= n[:, None]),
                             n[:, None] - age, _UNFILLED)
    out = dot_product_attention(
        q[:, None], ring_k[layer].at[rows, at].set(k_new),
        ring_v[layer].at[rows, at].set(v_new), causal=True,
        positions_q=jnp.where(active, n, _UNFILLED)[:, None],
        positions_kv=kv_positions)
    return out[:, 0]
