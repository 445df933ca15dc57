"""Block-paged KV cache with copy-on-write prefix sharing.

The vLLM-shape upgrade to the serving engine (ROADMAP item 2): instead
of one contiguous ``slot_len`` KV strip per decode slot, the cache is a
single pool of fixed-size **blocks** (``block_size`` tokens each) and
every slot owns a **block table** — a row of block indices whose
concatenation is that slot's logical KV strip. Two consequences:

- **Prefix sharing.** Blocks are content-addressed: a chained
  token-hash over the prompt (hash of ``tokens[:block_size]``, then
  ``tokens[:2*block_size]``, ...) keys each *full* block, plus one
  trailing key for the partial last block. Identical prefixes resolve
  to the same chain, so an 80%-shared system prompt is prefilled once
  and later requests just point their tables at the cached blocks
  (refcounted). Shared blocks are never written — a request that must
  write into a partially-filled shared block (its first generated
  token lands mid-block) **forks** it first: copy-on-write at the
  first write, counted in ``BlockPool.cow_forks``.
- **Packing.** Slot capacity stops being ``slots x worst-case
  length``: short requests hold few blocks, retired blocks return to
  the free pool, and prefix blocks whose refcount hits zero are
  *retained* in an LRU and only evicted when an allocation needs them.

Exactness contract (the whole point of the design): a slot's gathered
view — ``pool[k][:, table].reshape(...)`` — is byte-for-byte the
contiguous cache a solo ``generate_fused(prompt[None],
max_len=slot_len)`` call would build, because (a) prefill right-pads
(token *t* sits at offset *t*, preserving block alignment; pad columns
carry position ``_UNFILLED`` so the causal mask hides them), and
(b) splitting prefill at a cached-prefix boundary is bit-identical to
one wide chunk under XLA (verified in ``tests/test_paging.py``). So
per-request outputs stay bit-identical to solo ``generate_fused``,
cached prefix or not.

Layout notes: the decode step runs the ``_run_blocks`` trunk
(``models.decode``) but never builds a strip of the whole cache.
Each layer's attention reads that layer's blocks of the pool through
the slots' block tables (``ops/paged_attention.py``: on a one-device
TPU program a pallas kernel that copies only the blocks a live slot
has filled, elsewhere one layer's gather and the position-masked XLA
attention — the same values, the same math), the layer scan returns
only this token's new K/V column, and one scatter lands it in the
pool. Prefill still gathers its one request's strip. Two blocks are
reserved: block 0 is NULL (all-``_UNFILLED`` positions, the gather
target of unassigned table entries — never written) and block 1 is
SINK (the redirect target for writes that must go nowhere: inactive
rows' decode writes and install chunks that belong to shared blocks).
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_rm_tpu.models.decode import (
    _UNFILLED, _cache_attend, _run_blocks, _run_hybrid_blocks,
)
from kubeflow_rm_tpu.models.llama import LlamaConfig
from kubeflow_rm_tpu.ops import dot_product_attention
from kubeflow_rm_tpu.ops.paged_attention import (
    lane_dense, paged_decode_attention, window_decode_attention,
)

#: reserved block ids (see module docstring)
NULL_BLOCK = 0
SINK_BLOCK = 1
RESERVED_BLOCKS = 2


# ---------------------------------------------------------------------------
# device state
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclass
class PagedKVCache:
    """Pool-of-blocks KV state. ``block_tables[i]`` concatenated is
    slot *i*'s logical strip of ``slot_len = MAXB * BS`` positions;
    ``write_idx``/``pos_next`` are per-slot counters in logical-strip
    offsets, so each slot advances on its own.

    Where a head is narrower than the lanes and a token's heads fill
    them (``ops.paged_attention.lane_dense``: LFM2's 8 x 64) the pool
    keeps a token as one row of ``KVH * hd`` lanes, ``k`` and ``v``
    (L, NB, BS, KVH * hd): a block is then one region of the pool, as
    the decode kernel copies it, where the chip would keep a 5-D pool
    of such a head with the block index minor. Code that wants heads
    reads a block as ``block_shape`` and writes one as the pool keeps
    it."""
    k: jax.Array             # (L, NB, BS, KVH, hd) compute dtype
    v: jax.Array             # (L, NB, BS, KVH, hd)
    positions: jax.Array     # (NB, BS) int32; _UNFILLED marks empty
    block_tables: jax.Array  # (SLOTS, MAXB) int32; NULL_BLOCK = unset
    write_idx: jax.Array     # (SLOTS,) int32: next logical write slot
    pos_next: jax.Array      # (SLOTS,) int32: next token position
    kv_heads: int = field(metadata={"static": True})   # KVH

    @property
    def block_shape(self) -> tuple:
        """A block as the code that wants heads reads it: (BS, KVH,
        hd)."""
        BS, *token = self.k.shape[2:]
        return (BS, self.kv_heads, int(np.prod(token)) // self.kv_heads)


@jax.tree_util.register_dataclass
@dataclass
class HybridPagedCache:
    """Two kinds of state in one cache, for a config with recurrent
    layers (``cfg.has_recurrent_state``): the block pool of its
    attention layers (``kv``; its ``L`` is their number) and, a slot
    and recurrent layer each, the recurrent state and the convolution's
    tail, which are no strip of positions and live outside the pool;
    their shapes are the config's (``cfg.recurrent_shapes``: a Mamba-2
    layer's, a Gated DeltaNet layer's delta-rule state; ``ssm`` is None
    for a family whose only state is the tail, ``models.lfm2``'s short
    convolution).
    Admission writes a prefill's final state over whatever the slot
    held (``paged_install``); a decode step advances the live rows'
    and leaves the others' as it was. ``counters`` are the expert
    layers' own, accumulated on the device by the programs that donate
    the cache (row 0 the decode steps, row 1 the prefills, added at
    install): token-expert assignments routed to held experts, held
    experts with at least one token summed over layers, and programs
    run. int32: they wrap after 2^31, days of traffic; a reader takes
    differences. A config that ``counts_pairs`` has a fourth column:
    the (query, key) pairs one attention layer attended over the live
    slots (``WindowPagedCache``'s full layer's), which wrap within
    minutes of traffic: a reader takes differences modulo 2^32.

    A config that ``logs_routes`` keeps ``routes`` besides: the experts
    each column of each slot's strip chose, an expert layer each, as
    the programs chose them (install writes a prompt's, a decode step
    each live row's column), so that a check can follow the program's
    own routing; None for the other families."""
    kv: PagedKVCache
    ssm: jax.Array | None    # (Lr, SLOTS, heads, ...) f32
    conv: jax.Array          # (Lr, SLOTS, kernel - 1, channels)
    counters: jax.Array      # (2, 3) or (2, 4) int32
    routes: jax.Array | None = None   # (Le, SLOTS, S, top_k) int16


@jax.tree_util.register_dataclass
@dataclass
class WindowPagedCache:
    """Two kinds of attention state in one cache, for a config with
    sliding-window layers (``cfg.n_window_layers``): the block pool of
    its full attention layers (``kv``; its ``L`` is their number) and,
    a slot and window layer each, a ring of the last ``Wc`` positions'
    keys and values (``ring_len``: the window rounded up to whole
    blocks), position ``p`` at offset ``p % Wc``, overwritten in place
    as the context grows: a long context never grows it. A slot's count
    of tokens is its pool counters' (``kv.write_idx``: no prefix is
    adopted, so offset and position agree). Admission writes a
    prefill's last window over whatever the ring held
    (``paged_install``).

    ``counters`` (2, 5) int32 are the programs' own, accumulated on the
    device by the programs that donate the cache (row 0 the decode
    steps, row 1 the prefills, added at install): the expert layers'
    three (``HybridPagedCache``'s), then the (query, key) pairs one
    window layer and one full layer attended, over the live slots (a
    decode step: each slot's cached tokens and its own, the window's at
    most; a prefill: its real tokens'). The pairs wrap within minutes
    of traffic: a reader takes differences modulo 2^32."""
    kv: PagedKVCache
    ring_k: jax.Array        # (Lw, SLOTS, Wc, KVH, hd)
    ring_v: jax.Array
    counters: jax.Array      # (2, 5) int32


def ring_len(cfg, block_size: int) -> int:
    """Positions of a window layer's ring a slot: the window rounded up
    to whole blocks."""
    return -(-cfg.sliding_window // block_size) * block_size


def slot_state(cfg) -> str | None:
    """What a slot's cache holds that no block of tokens addresses, said
    for a refusal, or None: a prefix of such a cache cannot be adopted,
    exported or rewound."""
    if cfg.has_recurrent_state:
        return "recurrent layers, whose state is kept a slot (no snapshots yet)"
    if getattr(cfg, "n_window_layers", 0):
        return ("window layers, whose last positions are kept a slot in a "
                "ring overwritten in place (no block of it stays whole)")
    return None


def init_paged_cache(cfg: LlamaConfig, slots: int, slot_len: int,
                     num_blocks: int, block_size: int) -> PagedKVCache:
    """The cache of ``cfg``'s family: a ``PagedKVCache`` over every
    layer, for a config with recurrent layers a ``HybridPagedCache``
    whose pool serves the attention layers only, and for one with
    window layers a ``WindowPagedCache`` whose pool serves the full
    layers only."""
    if getattr(cfg, "n_window_layers", 0):
        shape = (cfg.n_window_layers, slots, ring_len(cfg, block_size),
                 cfg.n_kv_heads, cfg.head_dim)
        return WindowPagedCache(
            kv=_init_kv_pool(cfg, cfg.n_attention_layers, slots, slot_len,
                             num_blocks, block_size),
            ring_k=jnp.zeros(shape, cfg.dtype),
            ring_v=jnp.zeros(shape, cfg.dtype),
            counters=jnp.zeros((2, 5), jnp.int32))
    if cfg.has_recurrent_state:
        if not cfg.n_attention_layers:
            raise ValueError(f"pattern {cfg.pattern!r} has no attention "
                             "layer for the block pool to serve")
        state, tail = cfg.recurrent_shapes(slots)
        return HybridPagedCache(
            kv=_init_kv_pool(cfg, cfg.n_attention_layers, slots, slot_len,
                             num_blocks, block_size),
            ssm=None if state is None else jnp.zeros(state, jnp.float32),
            conv=jnp.zeros(tail, cfg.dtype),
            counters=jnp.zeros(
                (2, 3 + getattr(cfg, "counts_pairs", False)), jnp.int32),
            routes=(jnp.zeros((cfg.pattern.count("E"), slots, slot_len,
                               cfg.top_k), jnp.int16)
                    if getattr(cfg, "logs_routes", False) else None))
    return _init_kv_pool(cfg, cfg.n_layers, slots, slot_len, num_blocks,
                         block_size)


def _init_kv_pool(cfg, L: int, slots: int, slot_len: int,
                  num_blocks: int, block_size: int) -> PagedKVCache:
    if slot_len % block_size:
        raise ValueError(f"slot_len {slot_len} must be a multiple of "
                         f"block_size {block_size}")
    if num_blocks <= RESERVED_BLOCKS:
        raise ValueError(f"num_blocks {num_blocks} leaves no usable "
                         f"blocks ({RESERVED_BLOCKS} are reserved)")
    KVH, hd = cfg.n_kv_heads, cfg.head_dim
    maxb = slot_len // block_size
    token = (KVH * hd,) if lane_dense(KVH, hd) else (KVH, hd)
    return PagedKVCache(
        k=jnp.zeros((L, num_blocks, block_size, *token), cfg.dtype),
        v=jnp.zeros((L, num_blocks, block_size, *token), cfg.dtype),
        positions=jnp.full((num_blocks, block_size), _UNFILLED,
                           jnp.int32),
        block_tables=jnp.full((slots, maxb), NULL_BLOCK, jnp.int32),
        write_idx=jnp.zeros((slots,), jnp.int32),
        pos_next=jnp.zeros((slots,), jnp.int32),
        kv_heads=KVH,
    )


# ---------------------------------------------------------------------------
# jitted ops
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def paged_decode_step(params, cfg, cache: PagedKVCache, tokens, active):
    """One decode step over every slot, the pool read in place.

    ``tokens`` (B,) int32 is each slot's freshly-sampled token;
    ``active`` (B,) bool masks live slots. Each active row attends at
    its own ``pos_next`` over its logical (slot_len-long) strip and
    writes K/V at its own ``write_idx``; inactive rows still flow
    through the matmuls (static shapes) with query position
    ``_UNFILLED``, their counters do not advance, and their (garbage)
    pool write is redirected to SINK_BLOCK — their table may reference
    blocks that other slots now own, so their write target is NOT
    private and must be diverted. Returns (last-position logits (B, V)
    fp32, updated cache).

    The pool never rides the layer scan: the scan carries ``x``, scans
    (layer weights, layer index) and each layer's attention reads that
    layer's blocks of the pool through the block table
    (``ops.paged_attention``), this token's K/V joining as the strip's
    newest column. The scan's output is only that column, a layer
    each; one scatter after the scan lands it in the pool.
    """
    if isinstance(cache, HybridPagedCache):
        return _hybrid_decode_step(params, cfg, cache, tokens, active)
    if isinstance(cache, WindowPagedCache):
        return _window_decode_step(params, cfg, cache, tokens, active)
    positions, wi, blk, off, kv_positions = _decode_columns(cache, active)
    logits, (col_k, col_v) = _run_blocks(
        params, cfg, tokens[:, None], positions,
        jnp.arange(cache.k.shape[0], dtype=jnp.int32),
        _pool_attend(cache, positions, wi, active, kv_positions))
    new_cache = _land_columns(cache, col_k, col_v, blk, off, active)
    return logits[:, -1, :], new_cache


def _decode_columns(cache: PagedKVCache, active):
    """Where a decode step reads and writes: each row's query position
    (``_UNFILLED`` where inactive), its write index in its strip, the
    pool block and offset that index falls in (SINK where inactive) and
    every slot's strip of positions with this token's among them."""
    B, MAXB = cache.block_tables.shape
    BS = cache.positions.shape[1]
    S = MAXB * BS
    rows = jnp.arange(B, dtype=jnp.int32)

    positions = jnp.where(active, cache.pos_next, _UNFILLED)[:, None]
    wi = jnp.clip(cache.write_idx, 0, S - 1)
    blk = jnp.where(active, cache.block_tables[rows, wi // BS],
                    SINK_BLOCK)
    off = wi % BS

    # every slot's logical strip of positions, gathered through its
    # block table, this token's among them: the attention mask
    gpos = cache.positions[cache.block_tables].reshape(B, S)
    kv_positions = gpos.at[rows, wi].set(positions[:, 0])
    return positions, wi, blk, off, kv_positions


def _pool_attend(cache: PagedKVCache, positions, wi, active, kv_positions):
    """The decode step's ``attend``: layer ``layer``'s blocks read in
    place through the block table, this token's K/V as the newest
    column; the column is the layer's output."""
    def attend(q, k, v, layer):
        attn = paged_decode_attention(
            q[:, 0], k[:, 0], v[:, 0], cache.k, cache.v, layer,
            cache.block_tables, wi, active,
            positions_q=positions[:, 0], kv_positions=kv_positions)
        return attn[:, None], (k[:, 0], v[:, 0])
    return attend


def _land_columns(cache: PagedKVCache, col_k, col_v, blk, off, active):
    """Scatter the written column, (L, B, KVH, hd), into the pool
    (inactive rows land in SINK; duplicate sink hits are
    garbage-on-garbage) and advance the live rows' counters."""
    inc = active.astype(jnp.int32)
    at = (slice(None), blk, off)
    if cache.k.ndim == 4:
        # a lane-dense pool's token is a row of a tile: with a window
        # of (L, KVH * hd) the chip's scatter would relay the whole
        # pool to put L minor; with the layer indexed too it writes
        # the rows in place
        at = (jnp.arange(cache.k.shape[0])[:, None], blk, off)
    return PagedKVCache(
        k=cache.k.at[at].set(col_k.reshape(*col_k.shape[:2],
                                           *cache.k.shape[3:])),
        v=cache.v.at[at].set(col_v.reshape(*col_v.shape[:2],
                                           *cache.v.shape[3:])),
        positions=cache.positions.at[blk, off].set(
            jnp.where(active, cache.pos_next, _UNFILLED)),
        block_tables=cache.block_tables,
        write_idx=cache.write_idx + inc,
        pos_next=cache.pos_next + inc,
        kv_heads=cache.kv_heads,
    )


def _hybrid_decode_step(params, cfg, cache: HybridPagedCache, tokens,
                        active):
    """``paged_decode_step`` for a config with recurrent layers: the
    attention layers as above over ``cache.kv``; a Mamba layer takes
    one step of its recurrence from the slot's state, live rows only
    (an inactive row's state and tail come out as they went in, not
    garbage: the slot may be reseated later, or stay retired); the
    expert layers see the live rows only and add to the counters, and
    where the cache counts them so do the pairs one attention layer
    attended (a live slot's cached tokens and its own), and where the
    cache logs them each live row's choice of experts at its column."""
    kv = cache.kv
    positions, wi, blk, off, kv_positions = _decode_columns(kv, active)
    routes = None if cache.routes is None else []
    logits, (ssm, conv), cols, counts = _run_hybrid_blocks(
        params, cfg, tokens[:, None], active[:, None], positions, cache.ssm,
        cache.conv, _pool_attend(kv, positions, wi, active, kv_positions),
        routes=routes)
    col_k, col_v = (jnp.stack(c) for c in zip(*cols))
    if cache.counters.shape[1] > 3:
        pairs = jnp.sum(active.astype(jnp.int32) * (kv.write_idx + 1))
        counts = jnp.concatenate([counts, pairs[None]])
    log = cache.routes
    if log is not None:
        rows = jnp.arange(active.shape[0])
        col = jnp.where(active, wi, log.shape[2])     # idle rows: dropped
        log = log.at[:, rows, col].set(
            jnp.stack(routes)[:, :, 0].astype(log.dtype), mode="drop")
    return logits[:, -1, :], HybridPagedCache(
        kv=_land_columns(kv, col_k, col_v, blk, off, active),
        ssm=ssm, conv=conv, counters=cache.counters.at[0].add(counts),
        routes=log)


def _by_kind(cfg, cols):
    """The trunk's attention outputs, in pattern order, split into the
    full layers' and the window layers' (each stacked)."""
    kinds = [c for c in cfg.pattern if c in "WF"]
    return tuple(tuple(jnp.stack(c) for c in zip(
        *[y for kind, y in zip(kinds, cols) if kind == want]))
        for want in "FW")


def _window_pairs(n, window: int):
    """(query, key) pairs a causal window of ``window`` holds over the
    first ``n`` positions."""
    inside = jnp.minimum(n, window)
    return inside * (inside + 1) // 2 + (n - inside) * window


def _window_decode_step(params, cfg, cache: WindowPagedCache, tokens,
                        active):
    """``paged_decode_step`` for a config with window layers: the full
    layers as above over ``cache.kv``; a window layer reads its ring
    (``ops.paged_attention.window_decode_attention``: the window's
    positions at most, however long the context) and its new column
    lands at ``n % Wc`` of a live row's ring (an inactive row's ring is
    left as it was); the expert layers and the pairs each kind attended
    add to the counters."""
    kv = cache.kv
    W, Wc = cfg.sliding_window, cache.ring_k.shape[2]
    n = kv.write_idx
    positions, wi, blk, off, kv_positions = _decode_columns(kv, active)

    def attend_window(q, k, v, i):
        attn = window_decode_attention(
            q[:, 0], k[:, 0], v[:, 0], cache.ring_k, cache.ring_v, i, n,
            active, window=W)
        return attn[:, None], (k[:, 0], v[:, 0])

    logits, _state, cols, counts = _run_hybrid_blocks(
        params, cfg, tokens[:, None], active[:, None], positions, None, None,
        _pool_attend(kv, positions, wi, active, kv_positions),
        attend_window)
    (col_k, col_v), (ring_col_k, ring_col_v) = _by_kind(cfg, cols)
    rows = jnp.arange(n.shape[0], dtype=jnp.int32)
    at = jnp.where(active, jnp.remainder(n, Wc), Wc)   # Wc: dropped
    live = active.astype(jnp.int32)
    pairs = jnp.stack([jnp.sum(live * jnp.minimum(n + 1, W)),
                       jnp.sum(live * (n + 1))])
    return logits[:, -1, :], WindowPagedCache(
        kv=_land_columns(kv, col_k, col_v, blk, off, active),
        ring_k=cache.ring_k.at[:, rows, at].set(ring_col_k, mode="drop"),
        ring_v=cache.ring_v.at[:, rows, at].set(ring_col_v, mode="drop"),
        counters=cache.counters.at[0].add(
            jnp.concatenate([counts, pairs])))


# cache is READ-ONLY here: prefill gathers the shared-prefix strip
# out of the pool and writes a fresh single-request row cache; the
# pool has no successor to alias, and donating it would free buffers
# the engine still serves other slots from.
@partial(jax.jit, static_argnames=("cfg",))
def paged_prefill(params, cfg, cache: PagedKVCache,  # kfrm: disable=KFRM008
                  load_row, n_hit, tokens, n_real):
    """Prefill one request's suffix against its cached prefix.

    ``load_row`` (MAXB,) names the SOURCE blocks of the shared prefix
    (chunks beyond it are NULL); the gathered strip is truncated to
    ``n_hit`` tokens (everything at/after ``n_hit`` reads
    ``_UNFILLED`` — a partially-reused source block may carry another
    request's live tokens past the shared region, and truncation is
    what makes borrowing it safe). ``tokens`` (1, Tc) is the
    right-pad-bucketed suffix whose first ``n_real`` columns are real;
    it runs at offsets ``n_hit .. n_hit+Tc``. Returns the last REAL
    token's logits plus the full temp strip (k, v, positions) for
    ``paged_install`` to carve into blocks.

    Split-at-``n_hit`` prefill is bit-identical to one full-width
    chunk, and right-pad columns (position ``_UNFILLED``) leave real
    columns bit-identical — both properties are what lets a cached
    prefix + suffix prefill replace solo prefill exactly.
    """
    if isinstance(cache, HybridPagedCache):
        return _hybrid_prefill(params, cfg, cache, load_row, n_hit, tokens,
                               n_real)
    if isinstance(cache, WindowPagedCache):
        return _window_prefill(params, cfg, cache, tokens, n_real)
    strips, positions, kv_positions, write_kv = _prefix_strip(
        cache, load_row, n_hit, tokens.shape[1], n_real)
    logits, (new_k, new_v) = _run_blocks(
        params, cfg, tokens, positions, strips,
        _cache_attend(write_kv, positions, kv_positions))
    last = logits[0, n_real - 1, :]
    return last, new_k, new_v, kv_positions


def _prefix_strip(cache: PagedKVCache, load_row, n_hit, Tc, n_real):
    """The one-request strip a prefill runs against: the prefix's
    blocks gathered and cut at ``n_hit``, the suffix's positions (pad
    columns ``_UNFILLED``), the strip's positions with the suffix in
    place, and the write of a layer's K/V at ``n_hit``."""
    L = cache.k.shape[0]
    MAXB, BS = load_row.shape[0], cache.positions.shape[1]
    S = MAXB * BS

    gk = cache.k[:, load_row].reshape(L, 1, S, *cache.block_shape[1:])
    gv = cache.v[:, load_row].reshape(L, 1, S, *cache.block_shape[1:])
    gpos = cache.positions[load_row].reshape(1, S)
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    gpos = jnp.where(idx < n_hit, gpos, _UNFILLED)

    positions = n_hit + jnp.arange(Tc, dtype=jnp.int32)[None, :]
    positions = jnp.where(jnp.arange(Tc)[None, :] < n_real, positions,
                          _UNFILLED)
    kv_positions = jax.lax.dynamic_update_slice(gpos, positions,
                                                (0, n_hit))

    def write_kv(c, val):
        return jax.lax.dynamic_update_slice(c, val, (0, n_hit, 0, 0))

    return (gk, gv), positions, kv_positions, write_kv


def _hybrid_prefill(params, cfg, cache: HybridPagedCache, load_row, n_hit,
                    tokens, n_real):
    """``paged_prefill`` for a config with recurrent layers. The whole
    prompt is prefilled from an empty state (``n_hit`` is 0: a state is
    not addressable by token block, so the engine takes no prefix hit
    for this family); the pad columns of the bucket advance no state,
    so what comes back beside the strip is the state after the last
    REAL token, with the expert layers' counts (and, where the cache
    counts them, the prompt's causal pairs; where it logs them, the
    experts each column chose, (Le, Tc, top_k)), for ``paged_install``."""
    Tc = tokens.shape[1]
    (gk, gv), positions, kv_positions, write_kv = _prefix_strip(
        cache.kv, load_row, n_hit, Tc, n_real)
    strip_attend = _cache_attend(write_kv, positions, kv_positions)
    routes = None if cache.routes is None else []
    logits, (ssm, conv), strips, counts = _run_hybrid_blocks(
        params, cfg, tokens, jnp.arange(Tc)[None, :] < n_real, positions,
        None if cache.ssm is None else jnp.zeros_like(cache.ssm[:, :1]),
        jnp.zeros_like(cache.conv[:, :1]),
        lambda q, k, v, i: strip_attend(q, k, v, (gk[i], gv[i])),
        routes=routes)
    new_k, new_v = (jnp.stack(c) for c in zip(*strips))
    if cache.counters.shape[1] > 3:
        pairs = n_real * (n_real + 1) // 2
        counts = jnp.concatenate([counts, pairs.astype(jnp.int32)[None]])
    last = logits[0, n_real - 1, :]
    if routes is not None:
        return last, new_k, new_v, kv_positions, (
            ssm, conv, counts, jnp.stack(routes)[:, 0])
    return last, new_k, new_v, kv_positions, (ssm, conv, counts)


def _window_prefill(params, cfg, cache: WindowPagedCache, tokens, n_real):
    """``paged_prefill`` for a config with window layers. The whole
    prompt is prefilled (no prefix is adopted: a ring keeps no whole
    block), attending over the bucket itself and no strip: causal on
    the full layers, windowed on the window layers, through the flash
    forward on a one-device TPU program (``dot_product_attention``).
    The pad columns come after every real one, so no real query sees
    them. The head reads the last real column alone. Returns the full
    layers' strip as ``paged_prefill`` does and,
    for ``paged_install``, each window layer's ring of the prompt's
    last ``Wc`` positions (an offset the prompt never reached holds
    its first column: masked by position) with the counts."""
    Tc = tokens.shape[1]
    BS, KVH, hd = cache.kv.block_shape
    S = cache.kv.block_tables.shape[1] * BS
    Wc = cache.ring_k.shape[2]
    W = cfg.sliding_window
    col = jnp.arange(Tc, dtype=jnp.int32)[None, :]
    real = col < n_real
    positions = jnp.where(real, col, _UNFILLED)
    kv_positions = jnp.full((1, S), _UNFILLED, jnp.int32).at[:, :Tc].set(
        positions[:, :S])

    def attend(q, k, v, _i):
        strips = tuple(jnp.zeros((1, S, KVH, hd), x.dtype).at[:, :Tc].set(
            x[:, :S]) for x in (k, v))
        return dot_product_attention(q, k, v, causal=True), strips

    # offset o of the ring holds the prompt's latest position p = o
    # (mod Wc): n - 1 - ((n - 1 - o) mod Wc)
    offs = jnp.arange(Wc, dtype=jnp.int32)
    take = jnp.clip(n_real - 1 - jnp.remainder(n_real - 1 - offs, Wc),
                    0, Tc - 1)

    def attend_window(q, k, v, _i):
        return (dot_product_attention(q, k, v, causal=True, window=W),
                (k[:, take], v[:, take]))

    logits, _state, cols, counts = _run_hybrid_blocks(
        params, cfg, tokens, real, positions, None, None, attend,
        attend_window, head_at=n_real - 1)
    (new_k, new_v), rings = _by_kind(cfg, cols)
    pairs = jnp.stack([_window_pairs(n_real, W), n_real * (n_real + 1) // 2])
    last = logits[0, 0, :]
    return last, new_k, new_v, kv_positions, (
        *rings, jnp.concatenate([counts, pairs.astype(jnp.int32)]))


@partial(jax.jit, donate_argnames=("cache",))
def paged_install(cache: PagedKVCache, temp_k, temp_v, temp_pos, slot,
                  final_row, dest_row, write_idx0, prefilled=None):
    """Carve a prefilled temp strip into pool blocks and activate the
    slot. ``dest_row`` (MAXB,) maps each strip chunk to its pool
    destination: the request's OWN blocks for owned chunks, SINK for
    chunks it shares (already in the pool — never overwrite a shared
    block) and for tail chunks past its allocation. Every owned block
    is fully overwritten — positions included — which is the
    no-stale-reads guarantee for recycled blocks: whatever a block held
    before, after install its visible state is exactly the fresh
    strip's. ``write_idx0`` seats both counters at the REAL prompt
    length, so the first generated token overwrites the first pad
    column — the same offset solo ``generate_fused`` writes.

    For a ``HybridPagedCache``, ``prefilled`` is the prefill's (recurrent
    state, convolution tail, expert counts[, expert choices]): the
    first two replace whatever the slot held (the wipe a reseated slot
    relies on), the counts join the cache's counters, and the choices,
    where the cache logs them, land at the strip's first columns. For a ``WindowPagedCache`` it is
    the prefill's (rings, counts): each window layer's ring replaces
    the slot's."""
    if isinstance(cache, WindowPagedCache):
        ring_k, ring_v, counts = prefilled
        return WindowPagedCache(
            kv=_install_strip(cache.kv, temp_k, temp_v, temp_pos, slot,
                              final_row, dest_row, write_idx0),
            ring_k=cache.ring_k.at[:, slot].set(ring_k[:, 0]),
            ring_v=cache.ring_v.at[:, slot].set(ring_v[:, 0]),
            counters=cache.counters.at[1].add(counts))
    if isinstance(cache, HybridPagedCache):
        ssm, conv, counts, *chosen = prefilled
        log = cache.routes
        if log is not None:
            # a bucket wider than the strip: its columns past it are pads
            chosen = chosen[0][:, None, :log.shape[2]].astype(log.dtype)
            log = jax.lax.dynamic_update_slice(log, chosen, (0, slot, 0, 0))
        return HybridPagedCache(
            kv=_install_strip(cache.kv, temp_k, temp_v, temp_pos, slot,
                              final_row, dest_row, write_idx0),
            ssm=(None if ssm is None
                 else cache.ssm.at[:, slot].set(ssm[:, 0])),
            conv=cache.conv.at[:, slot].set(conv[:, 0]),
            counters=cache.counters.at[1].add(counts), routes=log)
    return _install_strip(cache, temp_k, temp_v, temp_pos, slot, final_row,
                          dest_row, write_idx0)


def _install_strip(cache: PagedKVCache, temp_k, temp_v, temp_pos, slot,
                   final_row, dest_row, write_idx0):
    L = cache.k.shape[0]
    MAXB, BS = dest_row.shape[0], cache.positions.shape[1]
    chunks_k = temp_k[:, 0].reshape(L, MAXB, *cache.k.shape[2:])
    chunks_v = temp_v[:, 0].reshape(L, MAXB, *cache.v.shape[2:])
    chunks_p = temp_pos[0].reshape(MAXB, BS)
    return PagedKVCache(
        k=cache.k.at[:, dest_row].set(chunks_k),
        v=cache.v.at[:, dest_row].set(chunks_v),
        positions=cache.positions.at[dest_row].set(chunks_p),
        block_tables=cache.block_tables.at[slot].set(final_row),
        write_idx=cache.write_idx.at[slot].set(write_idx0),
        pos_next=cache.pos_next.at[slot].set(write_idx0),
        kv_heads=cache.kv_heads,
    )


# debug/test helper: reads the pool into a contiguous strip for
# inspection — the cache must survive the call, donation would be a
# use-after-free for the engine.
@jax.jit
def gather_slot_strip(cache: PagedKVCache, slot):  # kfrm: disable=KFRM008
    """Debug/test helper: slot ``slot``'s logical strip as contiguous
    (k (L, S, KVH, hd), v, positions (S,)) arrays."""
    row = cache.block_tables[slot]
    L = cache.k.shape[0]
    MAXB, BS = row.shape[0], cache.positions.shape[1]
    k = cache.k[:, row].reshape(L, MAXB * BS, *cache.block_shape[1:])
    v = cache.v[:, row].reshape(L, MAXB * BS, *cache.block_shape[1:])
    pos = cache.positions[row].reshape(MAXB * BS)
    return k, v, pos


# ---------------------------------------------------------------------------
# host-side block accounting
# ---------------------------------------------------------------------------


def prefix_keys(tokens, block_size: int) -> list[tuple[int, bytes]]:
    """Chained content keys for a prompt: one per full-block boundary
    plus one for the trailing partial block. Key *i* digests
    ``tokens[: covered_i]`` — the whole prefix, not just the block —
    because a block's K/V depends on every token before it. Returns
    ``[(covered_tokens, key), ...]`` in chain order."""
    out: list[tuple[int, bytes]] = []
    arr = np.asarray(list(tokens), np.int32)
    h = hashlib.blake2b(digest_size=16)
    full = len(arr) // block_size
    for c in range(full):
        h.update(arr[c * block_size:(c + 1) * block_size].tobytes())
        out.append(((c + 1) * block_size, b"f" + h.digest()))
    if len(arr) % block_size:
        hp = h.copy()
        hp.update(arr[full * block_size:].tobytes())
        out.append((len(arr), b"p" + hp.digest()))
    return out


class BlockPool:
    """Refcounted free-list + content-addressed prefix index over the
    pool's block ids. Host-side only, driven by the single engine
    thread (callers serialize via the gateway lock) — no lock here.

    Lifecycle of a block: ``alloc`` (ref=1) → optionally ``register``
    under a prefix key (content-addressed, sharable) → ``incref`` per
    additional table that adopts it → ``decref`` per retiring table.
    At ref 0 an *unregistered* block returns to the free list
    immediately; a *registered* block is retained as prefix cache and
    only evicted — oldest first — when ``alloc`` runs dry. ``alloc``
    is atomic: it either returns ``n`` blocks or returns ``None``
    having changed nothing (the clean-OOM contract admission relies
    on)."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks <= RESERVED_BLOCKS:
            raise ValueError(
                f"num_blocks {num_blocks} leaves no usable blocks")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free: deque[int] = deque(range(RESERVED_BLOCKS,
                                             num_blocks))
        self._ref: dict[int, int] = {}
        self._index: OrderedDict[bytes, int] = OrderedDict()
        self._block_key: dict[int, bytes] = {}
        # chain linkage for registered keys: key -> predecessor key
        # (None at the chain head) and key -> covered-token count.
        # Export and promote-on-evict walk these to rebuild the chain
        # a key belongs to without re-hashing the prompt.
        self._parent: dict[bytes, bytes | None] = {}
        self._covered: dict[bytes, int] = {}
        #: optional ``fn(key, block)`` called just before a retained
        #: ref-0 prefix block is evicted, while its content is still
        #: in the device pool — the promote-to-global-store hook.
        self.on_evict = None
        self.cow_forks = 0
        self.evictions = 0
        self.alloc_failures = 0
        self.evict_hook_errors = 0

    # -- capacity ------------------------------------------------------

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - RESERVED_BLOCKS

    def free_count(self) -> int:
        return len(self._free)

    def evictable_count(self) -> int:
        return sum(1 for b in self._block_key
                   if self._ref.get(b, 0) == 0)

    def available(self) -> int:
        """Blocks an alloc could hand out right now: free + evictable
        retained prefix blocks."""
        return self.free_count() + self.evictable_count()

    def ref_of(self, block: int) -> int:
        return self._ref.get(block, 0)

    # -- alloc / refcount ----------------------------------------------

    def alloc(self, n: int) -> list[int] | None:
        """``n`` blocks at ref 1, or ``None`` with NO state change."""
        if n <= 0:
            return []
        if self.available() < n:
            self.alloc_failures += 1
            return None
        out: list[int] = []
        while len(out) < n:
            if self._free:
                b = self._free.popleft()
            else:
                b = self._evict_one()
            self._ref[b] = 1
            out.append(b)
        return out

    def _evict_one(self) -> int:
        for key, b in self._index.items():     # oldest entry first
            if self._ref.get(b, 0) == 0:
                if self.on_evict is not None:
                    # promotion reads the block from the device pool,
                    # so it must run BEFORE the id is handed out for
                    # reuse; a failing hook must never break alloc
                    try:
                        self.on_evict(key, b)
                    except Exception:  # kfrm: disable=KFRM005
                        # counted locally (evict_hook_errors): the
                        # models layer can't import controlplane
                        # metrics, and alloc must survive any hook
                        self.evict_hook_errors += 1
                del self._index[key]
                del self._block_key[b]
                self._parent.pop(key, None)
                self._covered.pop(key, None)
                self.evictions += 1
                return b
        raise RuntimeError("evict with no evictable block "
                           "(available() said otherwise)")

    def incref(self, blocks) -> None:
        for b in blocks:
            self._ref[b] = self._ref.get(b, 0) + 1
            key = self._block_key.get(b)
            if key is not None:                # LRU touch
                self._index.move_to_end(key)

    def decref(self, blocks) -> None:
        for b in blocks:
            r = self._ref.get(b, 0) - 1
            if r < 0:
                raise RuntimeError(f"decref of block {b} below zero")
            self._ref[b] = r
            if r == 0 and b not in self._block_key:
                self._free.append(b)

    # -- prefix index --------------------------------------------------

    def lookup(self, key: bytes) -> int | None:
        b = self._index.get(key)
        if b is not None:
            self._index.move_to_end(key)
        return b

    def register(self, key: bytes, block: int, *,
                 parent: bytes | None = None,
                 covered: int | None = None) -> int:
        """Publish ``block`` under ``key``; first writer wins (an
        identical prefix prefilled twice registers once — the second
        block simply frees on retire). ``parent``/``covered`` record
        the chain linkage used by export and promote-on-evict."""
        if parent is not None or key not in self._parent:
            self._parent[key] = parent
        if covered is not None:
            self._covered[key] = int(covered)
        existing = self._index.get(key)
        if existing is not None:
            self._index.move_to_end(key)
            return existing
        if block in self._block_key:           # one key per block
            return self._index[self._block_key[block]]
        self._index[key] = block
        self._block_key[block] = key
        return block

    def parent_of(self, key: bytes) -> bytes | None:
        return self._parent.get(key)

    def covered_of(self, key: bytes) -> int | None:
        return self._covered.get(key)

    def lookup_chain(self, keys) -> list[int]:
        """Longest CONSECUTIVE run of ``keys`` present in the index
        (a later hit without its predecessors is unusable — the table
        needs every chunk up to the hit). Returns the blocks."""
        out: list[int] = []
        for _covered, key in keys:
            b = self.lookup(key)
            if b is None:
                break
            out.append(b)
        return out

    def stats(self) -> dict:
        return {
            "blocks_total": self.usable_blocks,
            "blocks_free": self.free_count(),
            "blocks_evictable": self.evictable_count(),
            "blocks_available": self.available(),
            "free_block_fraction": (self.available()
                                    / max(1, self.usable_blocks)),
            "prefix_entries": len(self._index),
            "cow_forks": self.cow_forks,
            "evictions": self.evictions,
            "alloc_failures": self.alloc_failures,
        }


# ---------------------------------------------------------------------------
# chain export / import — replica-to-replica block transfer
# ---------------------------------------------------------------------------
# The chained ``prefix_keys`` hashes commit to the whole prefix, so a
# chain is a replica-agnostic name for its K/V content: any pool that
# prefilled the same tokens on the same weights holds bit-identical
# blocks under the same keys. A serialized chain carries the host
# copies of those blocks plus per-chunk checksums; ``import_chain``
# refuses a corrupted chunk without touching pool state, and a chain
# adopted into a foreign pool decodes bit-identically to solo
# ``generate_fused`` (tests/test_chain_transfer.py).


def _chunk_checksum(ck: np.ndarray, cv: np.ndarray,
                    cp: np.ndarray) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(np.ascontiguousarray(ck).tobytes())
    h.update(np.ascontiguousarray(cv).tobytes())
    h.update(np.ascontiguousarray(cp).tobytes())
    return h.digest()


def export_block_chunk(cache: PagedKVCache, block: int,
                       valid: int) -> dict:
    """Host copy of ONE pool block, sanitized past ``valid`` tokens
    (zero K/V, ``_UNFILLED`` positions) so the bytes — and therefore
    the checksum — depend only on the prefix the block's key names,
    never on whatever a later request generated into the tail."""
    L = cache.k.shape[0]
    ck = np.array(cache.k[:, block]).reshape(L, *cache.block_shape)
    cv = np.array(cache.v[:, block]).reshape(L, *cache.block_shape)
    cp = np.array(cache.positions[block], np.int32)
    ck[:, valid:] = 0
    cv[:, valid:] = 0
    cp[valid:] = _UNFILLED
    return {"k": ck, "v": cv, "pos": cp,
            "sum": _chunk_checksum(ck, cv, cp)}


def export_chain(cache: PagedKVCache, pool: BlockPool,
                 tokens) -> dict | None:
    """Serialize the pool's chain for ``tokens`` — every chunk's K/V,
    positions, keys, and checksums — or ``None`` if the pool does not
    hold the full chain. Tail columns past each chunk's covered count
    are sanitized, so identical prompts export identical bytes."""
    tokens = [int(t) for t in tokens]
    keys = prefix_keys(tokens, pool.block_size)
    blocks = pool.lookup_chain(keys)
    if len(blocks) < len(keys):
        return None
    BS = pool.block_size
    idx = jnp.asarray(blocks, jnp.int32)
    L, NC = cache.k.shape[0], len(blocks)
    ck = np.array(cache.k[:, idx]).reshape(L, NC, *cache.block_shape)
    cv = np.array(cache.v[:, idx]).reshape(L, NC, *cache.block_shape)
    cp = np.array(cache.positions[idx], np.int32)
    for i, (covered, _key) in enumerate(keys):
        valid = covered - i * BS
        ck[:, i, valid:] = 0
        cv[:, i, valid:] = 0
        cp[i, valid:] = _UNFILLED
    sums = [_chunk_checksum(ck[:, i], cv[:, i], cp[i])
            for i in range(len(keys))]
    return {
        "version": 1,
        "block_size": BS,
        "tokens": tokens,
        "covered": keys[-1][0],
        "keys": [k for _c, k in keys],
        "covers": [c for c, _k in keys],
        "chunks_k": ck,
        "chunks_v": cv,
        "chunks_pos": cp,
        "sums": sums,
        "nbytes": int(ck.nbytes + cv.nbytes + cp.nbytes),
    }


def verify_chain(chain: dict) -> None:
    """Raise ``ValueError`` unless the chain is internally consistent:
    chunk checksums match the payload, and — when the prompt rides
    along — the keys really are the chained hashes of the tokens.
    Checks mutate nothing, so a refusal leaves any pool untouched."""
    keys = list(chain.get("keys") or [])
    covers = list(chain.get("covers") or [])
    sums = list(chain.get("sums") or [])
    nc = len(keys)
    if not nc or len(covers) != nc or len(sums) != nc:
        raise ValueError("chain integrity: malformed key/cover/sum "
                         "lists")
    ck, cv, cp = (chain["chunks_k"], chain["chunks_v"],
                  chain["chunks_pos"])
    BS = int(chain["block_size"])
    if (ck.shape[1] != nc or cv.shape != ck.shape
            or cp.shape != (nc, BS) or ck.shape[2] != BS):
        raise ValueError("chain integrity: chunk shapes disagree "
                         "with the key list")
    tokens = chain.get("tokens")
    if tokens is not None:
        want = prefix_keys(tokens, BS)
        if ([k for _c, k in want] != keys
                or [c for c, _k in want] != covers):
            raise ValueError("chain integrity: keys are not the "
                             "chained hashes of the tokens")
    for i in range(nc):
        if _chunk_checksum(ck[:, i], cv[:, i], cp[i]) != sums[i]:
            raise ValueError(
                f"chain integrity: chunk {i} checksum mismatch")


def import_chain(cache: PagedKVCache, pool: BlockPool,
                 chain: dict) -> tuple[PagedKVCache, list[int]] | None:
    """Adopt a foreign chain: verify it, seat its chunks in freshly
    allocated blocks, and register every key. Returns the new cache
    plus the allocated blocks (ref 1 — the caller decrefs them to
    hand the chain to the LRU as retained prefix cache), or ``None``
    on clean OOM. Keys already registered locally keep their existing
    blocks; the redundant fresh block simply frees on decref."""
    verify_chain(chain)
    if int(chain["block_size"]) != pool.block_size:
        raise ValueError(
            f"chain block_size {chain['block_size']} != pool "
            f"block_size {pool.block_size}")
    nc = len(chain["keys"])
    if chain["chunks_k"].shape[0] != cache.k.shape[0] \
            or chain["chunks_k"].shape[2:] != cache.block_shape:
        raise ValueError("chain chunk shape does not fit this cache")
    blocks = pool.alloc(nc)
    if blocks is None:
        return None
    idx = jnp.asarray(blocks, jnp.int32)
    cache = PagedKVCache(
        k=cache.k.at[:, idx].set(jnp.asarray(
            chain["chunks_k"], cache.k.dtype).reshape(
                -1, nc, *cache.k.shape[2:])),
        v=cache.v.at[:, idx].set(jnp.asarray(
            chain["chunks_v"], cache.v.dtype).reshape(
                -1, nc, *cache.v.shape[2:])),
        positions=cache.positions.at[idx].set(
            jnp.asarray(chain["chunks_pos"], jnp.int32)),
        block_tables=cache.block_tables,
        write_idx=cache.write_idx,
        pos_next=cache.pos_next,
        kv_heads=cache.kv_heads,
    )
    parent = None
    for i, key in enumerate(chain["keys"]):
        pool.register(key, blocks[i], parent=parent,
                      covered=chain["covers"][i])
        parent = key
    return cache, blocks
