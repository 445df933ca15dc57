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
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_rm_tpu.models.decode import (
    _UNFILLED, _cache_attend, _run_blocks, _run_hybrid_blocks,
)
from kubeflow_rm_tpu.models.llama import LlamaConfig
from kubeflow_rm_tpu.ops.paged_attention import paged_decode_attention

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
    offsets, so each slot advances on its own."""
    k: jax.Array             # (L, NB, BS, KVH, hd) compute dtype
    v: jax.Array             # (L, NB, BS, KVH, hd)
    positions: jax.Array     # (NB, BS) int32; _UNFILLED marks empty
    block_tables: jax.Array  # (SLOTS, MAXB) int32; NULL_BLOCK = unset
    write_idx: jax.Array     # (SLOTS,) int32: next logical write slot
    pos_next: jax.Array      # (SLOTS,) int32: next token position


@jax.tree_util.register_dataclass
@dataclass
class HybridPagedCache:
    """Two kinds of state in one cache, for a config with recurrent
    layers (``cfg.has_recurrent_state``): the block pool of its
    attention layers (``kv``; its ``L`` is their number) and, a slot
    and Mamba layer each, the recurrent state and the convolution's
    tail, which are no strip of positions and live outside the pool.
    Admission writes a prefill's final state over whatever the slot
    held (``paged_install``); a decode step advances the live rows'
    and leaves the others' as it was. ``counters`` are the expert
    layers' own, accumulated on the device by the programs that donate
    the cache (row 0 the decode steps, row 1 the prefills, added at
    install): token-expert assignments routed to held experts, held
    experts with at least one token summed over layers, and programs
    run. int32: they wrap after 2^31, days of traffic; a reader takes
    differences."""
    kv: PagedKVCache
    ssm: jax.Array           # (Lm, SLOTS, heads, head_dim, state) f32
    conv: jax.Array          # (Lm, SLOTS, kernel - 1, conv_dim)
    counters: jax.Array      # (2, 3) int32


def init_paged_cache(cfg: LlamaConfig, slots: int, slot_len: int,
                     num_blocks: int, block_size: int) -> PagedKVCache:
    """The cache of ``cfg``'s family: a ``PagedKVCache`` over every
    layer, or for a config with recurrent layers a ``HybridPagedCache``
    whose pool serves the attention layers only."""
    if cfg.has_recurrent_state:
        Lm = cfg.pattern.count("M")
        if "*" not in cfg.pattern:
            raise ValueError(f"pattern {cfg.pattern!r} has no attention "
                             "layer for the block pool to serve")
        return HybridPagedCache(
            kv=_init_kv_pool(cfg, cfg.pattern.count("*"), slots, slot_len,
                             num_blocks, block_size),
            ssm=jnp.zeros((Lm, slots, cfg.mamba_heads, cfg.mamba_head_dim,
                           cfg.state_size), jnp.float32),
            conv=jnp.zeros((Lm, slots, cfg.conv_kernel - 1, cfg.conv_dim),
                           cfg.dtype),
            counters=jnp.zeros((2, 3), jnp.int32))
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
    return PagedKVCache(
        k=jnp.zeros((L, num_blocks, block_size, KVH, hd), cfg.dtype),
        v=jnp.zeros((L, num_blocks, block_size, KVH, hd), cfg.dtype),
        positions=jnp.full((num_blocks, block_size), _UNFILLED,
                           jnp.int32),
        block_tables=jnp.full((slots, maxb), NULL_BLOCK, jnp.int32),
        write_idx=jnp.zeros((slots,), jnp.int32),
        pos_next=jnp.zeros((slots,), jnp.int32),
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
    return PagedKVCache(
        k=cache.k.at[:, blk, off].set(col_k),
        v=cache.v.at[:, blk, off].set(col_v),
        positions=cache.positions.at[blk, off].set(
            jnp.where(active, cache.pos_next, _UNFILLED)),
        block_tables=cache.block_tables,
        write_idx=cache.write_idx + inc,
        pos_next=cache.pos_next + inc,
    )


def _hybrid_decode_step(params, cfg, cache: HybridPagedCache, tokens,
                        active):
    """``paged_decode_step`` for a config with recurrent layers: the
    attention layers as above over ``cache.kv``; a Mamba layer takes
    one step of its recurrence from the slot's state, live rows only
    (an inactive row's state and tail come out as they went in, not
    garbage: the slot may be reseated later, or stay retired); the
    expert layers see the live rows only and add to the counters."""
    kv = cache.kv
    positions, wi, blk, off, kv_positions = _decode_columns(kv, active)
    logits, (ssm, conv), cols, counts = _run_hybrid_blocks(
        params, cfg, tokens[:, None], active[:, None], cache.ssm,
        cache.conv, _pool_attend(kv, positions, wi, active, kv_positions))
    col_k, col_v = (jnp.stack(c) for c in zip(*cols))
    return logits[:, -1, :], HybridPagedCache(
        kv=_land_columns(kv, col_k, col_v, blk, off, active),
        ssm=ssm, conv=conv, counters=cache.counters.at[0].add(counts))


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

    gk = cache.k[:, load_row].reshape(L, 1, S, *cache.k.shape[3:])
    gv = cache.v[:, load_row].reshape(L, 1, S, *cache.v.shape[3:])
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
    REAL token, with the expert layers' counts, for ``paged_install``."""
    Tc = tokens.shape[1]
    (gk, gv), positions, kv_positions, write_kv = _prefix_strip(
        cache.kv, load_row, n_hit, Tc, n_real)
    strip_attend = _cache_attend(write_kv, positions, kv_positions)
    logits, (ssm, conv), strips, counts = _run_hybrid_blocks(
        params, cfg, tokens, jnp.arange(Tc)[None, :] < n_real,
        jnp.zeros_like(cache.ssm[:, :1]), jnp.zeros_like(cache.conv[:, :1]),
        lambda q, k, v, i: strip_attend(q, k, v, (gk[i], gv[i])))
    new_k, new_v = (jnp.stack(c) for c in zip(*strips))
    last = logits[0, n_real - 1, :]
    return last, new_k, new_v, kv_positions, (ssm, conv, counts)


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
    state, convolution tail, expert counts): the first two replace
    whatever the slot held (the wipe a reseated slot relies on), the
    counts join the cache's counters."""
    if isinstance(cache, HybridPagedCache):
        ssm, conv, counts = prefilled
        return HybridPagedCache(
            kv=_install_strip(cache.kv, temp_k, temp_v, temp_pos, slot,
                              final_row, dest_row, write_idx0),
            ssm=cache.ssm.at[:, slot].set(ssm[:, 0]),
            conv=cache.conv.at[:, slot].set(conv[:, 0]),
            counters=cache.counters.at[1].add(counts))
    return _install_strip(cache, temp_k, temp_v, temp_pos, slot, final_row,
                          dest_row, write_idx0)


def _install_strip(cache: PagedKVCache, temp_k, temp_v, temp_pos, slot,
                   final_row, dest_row, write_idx0):
    L = cache.k.shape[0]
    MAXB, BS = dest_row.shape[0], cache.positions.shape[1]
    chunks_k = temp_k[:, 0].reshape(L, MAXB, BS, *temp_k.shape[3:])
    chunks_v = temp_v[:, 0].reshape(L, MAXB, BS, *temp_v.shape[3:])
    chunks_p = temp_pos[0].reshape(MAXB, BS)
    return PagedKVCache(
        k=cache.k.at[:, dest_row].set(chunks_k),
        v=cache.v.at[:, dest_row].set(chunks_v),
        positions=cache.positions.at[dest_row].set(chunks_p),
        block_tables=cache.block_tables.at[slot].set(final_row),
        write_idx=cache.write_idx.at[slot].set(write_idx0),
        pos_next=cache.pos_next.at[slot].set(write_idx0),
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
    k = cache.k[:, row].reshape(L, MAXB * BS, *cache.k.shape[3:])
    v = cache.v[:, row].reshape(L, MAXB * BS, *cache.v.shape[3:])
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
    ck = np.array(cache.k[:, block])           # (L, BS, KVH, hd)
    cv = np.array(cache.v[:, block])
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
    ck = np.array(cache.k[:, idx])             # (L, NC, BS, KVH, hd)
    cv = np.array(cache.v[:, idx])
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
            or chain["chunks_k"].shape[2:] != cache.k.shape[2:]:
        raise ValueError("chain chunk shape does not fit this cache")
    blocks = pool.alloc(nc)
    if blocks is None:
        return None
    idx = jnp.asarray(blocks, jnp.int32)
    cache = PagedKVCache(
        k=cache.k.at[:, idx].set(
            jnp.asarray(chain["chunks_k"], cache.k.dtype)),
        v=cache.v.at[:, idx].set(
            jnp.asarray(chain["chunks_v"], cache.v.dtype)),
        positions=cache.positions.at[idx].set(
            jnp.asarray(chain["chunks_pos"], jnp.int32)),
        block_tables=cache.block_tables,
        write_idx=cache.write_idx,
        pos_next=cache.pos_next,
    )
    parent = None
    for i, key in enumerate(chain["keys"]):
        pool.register(key, blocks[i], parent=parent,
                      covered=chain["covers"][i])
        parent = key
    return cache, blocks
