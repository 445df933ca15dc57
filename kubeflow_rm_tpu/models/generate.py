"""KV-cached autoregressive generation for the Llama family.

The in-notebook inference path: prefill the prompt in one pass, then
decode a token per step against a preallocated static-shape cache —
every step is the SAME jitted computation (no data-dependent shapes),
which is what XLA wants on TPU. Exactness against the training
``forward`` is asserted by ``tests/test_generate.py``.

Three modules, imports one way: ``models.decode`` holds the trunk
(``KVCache``, ``decode_chunk``, ``_run_blocks``), ``models.paging`` the
block pool and its jitted steps over that trunk, and this module what
drives them: the generation loops (``generate``, ``generate_fused``,
the speculative program, their sharded builders) and the serving
engine, ``ContinuousBatchingEngine``, over the block pool.

The reference platform ships no model runtime at all; this module is
capability the jupyter-jax image adds on top (SURVEY.md §2.6).
"""

from __future__ import annotations

import contextlib
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_rm_tpu.analysis.jaxcheck import recompile as _jit_sentinel
from kubeflow_rm_tpu.models import paging
from kubeflow_rm_tpu.models.decode import (
    _UNFILLED, KVCache, decode_chunk, init_cache, serving_layout,
)
from kubeflow_rm_tpu.models.llama import LlamaConfig
from kubeflow_rm_tpu.models.quantize import (
    has_int4_leaf, unpack_int4_params,
)
from kubeflow_rm_tpu.ops.paged_attention import chunk_blocks, whole_chunks
from kubeflow_rm_tpu.utils.profiling import annotate as _span


def cache_shardings(cfg: LlamaConfig, mesh) -> KVCache:
    """NamedSharding pytree for a KVCache on ``mesh``: batch over
    (dp, fsdp), KV heads over tp — the decode-time analogue of
    ``parallel.sharding`` (weights stay on their training shardings)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return KVCache(
        k=NamedSharding(mesh, P(None, ("dp", "fsdp"), None, "tp", None)),
        v=NamedSharding(mesh, P(None, ("dp", "fsdp"), None, "tp", None)),
        positions=NamedSharding(mesh, P(("dp", "fsdp"), None)),
        offset=NamedSharding(mesh, P()),
    )


def make_decode_step(example_params: dict, cfg: LlamaConfig, mesh):
    """Jitted sharded ``(params, cache, tokens) -> (logits, cache)``.

    Params carry their training shardings (``parallel.sharding`` rules
    — serve on an fsdp×tp mesh), the cache follows ``cache_shardings``
    and is donated so decode runs in-place in HBM; logits come back
    vocab-sharded over tp. ``example_params`` is only inspected for the
    pytree structure. Exactness vs the unsharded path is asserted by
    ``tests/test_generate.py``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubeflow_rm_tpu.parallel.sharding import (
        batch_pspec, param_shardings,
    )

    return jax.jit(
        lambda p, cache, tokens: decode_chunk(p, cfg, cache, tokens),
        in_shardings=(param_shardings(example_params, mesh),
                      cache_shardings(cfg, mesh),
                      NamedSharding(mesh, batch_pspec(False))),
        out_shardings=(NamedSharding(mesh, P(("dp", "fsdp"), None, "tp")),
                       cache_shardings(cfg, mesh)),
        donate_argnums=(1,),
    )


def _pick(last, key, *, temperature, top_k):
    """Next-token choice from last-position logits (B, V): greedy
    argmax at temperature 0, else (top-k-truncated) categorical. The
    single source for BOTH decode paths — ``generate`` and
    ``_fused_generate`` must sample identically or the fused path's
    greedy bit-identity guarantee silently breaks."""
    if temperature <= 0:
        return jnp.argmax(last, axis=-1).astype(jnp.int32)
    scaled = last / temperature
    if top_k:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def _decode_step(params, cfg, cache, tokens, pad_counts=None):
    """Module-level jitted ``decode_chunk``: one cache entry per
    (config, shapes), shared across ``generate`` calls — a per-call
    ``jax.jit(lambda ...)`` would be a fresh cache key every time and
    re-trace + re-compile on every generation."""
    return decode_chunk(params, cfg, cache, tokens, pad_counts)


def _fused_decode_loop(params, cfg, prompt, key, *, max_new_tokens,
                       temperature, top_k, eos_id, total_len,
                       cache_sharding=None, pad_counts=None):
    """Trace-time body shared by ``generate_fused`` (single device) and
    ``make_generate_step`` (sharded): prefill, then a ``lax.scan`` over
    decode steps. ``cache_sharding`` (a NamedSharding pytree) pins the
    freshly-initialized cache's layout under GSPMD.

    Packed-int4 params are unpacked to int8 groups HERE — before the
    scan, so the nibble unpack happens once per generation instead of
    once per token (the per-step cost drops to the int8→bf16 dequant
    prologue; dequant on the unpacked form is bit-identical to dequant
    on the packed form, see ``quantize.unpack_int4``)."""
    params = unpack_int4_params(params)
    B, _ = prompt.shape
    cache = init_cache(cfg, B, total_len)
    if cache_sharding is not None:
        cache = jax.lax.with_sharding_constraint(cache, cache_sharding)
    logits, cache = decode_chunk(params, cfg, cache, prompt, pad_counts)
    last = logits[:, -1, :]

    def body(carry, k_i):
        cache, last, done = carry
        nxt = _pick(last, k_i, temperature=temperature, top_k=top_k)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        logits, cache = decode_chunk(params, cfg, cache, nxt[:, None],
                                     pad_counts)
        return (cache, logits[:, -1, :], done), nxt

    keys = jax.random.split(key, max_new_tokens)
    (_, _, _), toks = jax.lax.scan(
        body, (cache, last, jnp.zeros((B,), bool)), keys)
    return jnp.concatenate([prompt, toks.T], axis=1)


@partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "temperature", "top_k", "eos_id",
    "total_len"))
def _fused_generate(params, prompt, key, pad_counts=None, *, cfg,
                    max_new_tokens, temperature, top_k, eos_id,
                    total_len):
    return _fused_decode_loop(
        params, cfg, prompt, key, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, eos_id=eos_id,
        total_len=total_len, pad_counts=pad_counts)


def generate_fused(params: dict, cfg: LlamaConfig, prompt: jax.Array, *,
                   max_new_tokens: int, key: jax.Array | None = None,
                   temperature: float = 0.0, top_k: int | None = None,
                   eos_id: int | None = None,
                   max_len: int | None = None,
                   pad_counts: jax.Array | None = None) -> jax.Array:
    """``generate`` as ONE compiled XLA program.

    The Python-loop ``generate`` dispatches a jitted step per token and
    pays a host round trip for each. Here the whole prefill +
    ``lax.scan`` decode loop (sampling, eos latching, cache updates
    included) lowers to a single jit, so dispatch cost is paid once per
    generation instead of once per token. Greedy output is
    bit-identical to ``generate``; at ``temperature > 0`` the PRNG
    stream differs (keys are pre-split for the scan), which is the only
    behavioral difference.

    The scan runs exactly ``max_new_tokens`` steps; the final step's
    cache write is dead work (~1/N overhead) — the price of a
    shape-static loop, which is what keeps the whole thing one program.

    ``pad_counts`` (B,) marks each row's leading slots as left-padding
    for ragged batches: masked out of attention and position-shifted
    so output rows are bit-identical to unpadded per-row calls (the
    serving batcher's correctness contract — see ``decode_chunk``).
    """
    B, Tp = prompt.shape
    S = max_len or (Tp + max_new_tokens)
    if S < Tp + max_new_tokens:
        raise ValueError(
            f"max_len={S} < prompt {Tp} + new {max_new_tokens}")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    return _fused_generate(
        params, prompt, key if key is not None else jax.random.key(0),
        pad_counts,
        cfg=cfg, max_new_tokens=max_new_tokens,
        temperature=float(temperature), top_k=top_k, eos_id=eos_id,
        total_len=S)


def rewind_cache(cache: KVCache, new_offset) -> KVCache:
    """Logically truncate the cache to ``new_offset`` filled slots.

    Slots at/after ``new_offset`` get position ``_UNFILLED`` — the
    causal mask then excludes their (stale) K/V from every future
    query, so physical K/V bytes need no clearing. O(B·S) positions
    traffic, no weight traffic. The speculative decoder uses this to
    drop rejected draft tokens."""
    idx = jnp.arange(cache.positions.shape[1], dtype=jnp.int32)
    pos = jnp.where(idx[None, :] >= new_offset, _UNFILLED,
                    cache.positions)
    return KVCache(k=cache.k, v=cache.v, positions=pos,
                   offset=jnp.asarray(new_offset, jnp.int32))


@partial(jax.jit, static_argnames=("cfg", "max_new_tokens", "lookup_n",
                                   "draft_k", "eos_id", "total_len"))
def _fused_speculative(params, prompt, *, cfg, max_new_tokens,
                       lookup_n, draft_k, eos_id, total_len):
    """The whole speculative loop as ONE XLA program (batch 1).

    Decode is weights-bound, so verifying a (draft_k+1)-wide chunk
    costs roughly the same HBM traffic as a width-1 step — widening is
    nearly free ON-DEVICE. What ruins host-side speculation is the
    blocking sync every round (lookup + accept decisions on the
    host); here the n-gram match, draft gather,
    verification, cache rewind and loop all run under
    ``lax.while_loop``, so the host dispatches once per generation.
    Worst case (nothing accepts) each round still commits 1 token at
    chunk cost ≈ step cost; best case commits draft_k+1.
    """
    params = unpack_int4_params(params)  # int4 once, not per round
    Tp = prompt.shape[1]
    W = draft_k + 1
    S = total_len  # buffer/cache length, incl. chunk overhang room
    V = cfg.vocab_size
    target = Tp + max_new_tokens

    buf = jnp.zeros((S,), jnp.int32).at[:Tp].set(prompt[0])
    cache = init_cache(cfg, 1, S)
    logits, cache = decode_chunk(params, cfg, cache, prompt)
    last = logits[0, -1, :]

    def cond(carry):
        buf, count, cache, last, done, rounds = carry
        return (count < target) & ~done

    def body(carry):
        buf, count, cache, last, done, rounds = carry
        nxt = jnp.argmax(last).astype(jnp.int32)
        buf = buf.at[count].set(nxt)
        count = count + 1

        # prompt-lookup on device: most recent earlier occurrence of
        # the trailing n-gram; its followers become the draft
        tail = jax.lax.dynamic_slice(buf, (count - lookup_n,),
                                     (lookup_n,))
        idx = jnp.arange(S, dtype=jnp.int32)
        windows = buf[jnp.minimum(idx[:, None]
                                  + jnp.arange(lookup_n)[None, :],
                                  S - 1)]
        hit = (windows == tail[None, :]).all(-1) & (idx < count
                                                    - lookup_n)
        has_hit = hit.any()
        p = jnp.max(jnp.where(hit, idx, -1))  # most recent match
        start = jnp.where(has_hit, p + lookup_n, 0)
        draft = jax.lax.dynamic_slice(
            jnp.pad(buf, (0, W)), (start,), (draft_k,))
        # no hit → draft vs greedy will disagree, costing nothing
        # extra: the chunk runs at width W every round regardless

        chunk = jnp.concatenate([nxt[None], draft])[None, :]  # (1, W)
        logits, cache = decode_chunk(params, cfg, cache, chunk)
        greedy = jnp.argmax(logits[0], axis=-1).astype(jnp.int32)

        # accept the longest prefix of drafts matching greedy
        ok = jnp.cumprod((draft == greedy[:-1]).astype(jnp.int32))
        budget = jnp.clip(target - count, 0, draft_k)
        accepted = jnp.minimum(jnp.sum(ok), budget)
        wpos = count + jnp.arange(draft_k)
        wmask = jnp.arange(draft_k) < accepted
        buf = buf.at[jnp.minimum(wpos, S - 1)].set(
            jnp.where(wmask, draft, buf[jnp.minimum(wpos, S - 1)]))
        count = count + accepted

        if eos_id is not None:
            committed = jnp.concatenate([nxt[None], draft])
            cmask = jnp.arange(W) < (1 + accepted)
            is_eos = (committed == eos_id) & cmask
            done = done | is_eos.any()

        # drop the rejected tail: stale K/V is masked via positions
        cache = rewind_cache(cache, cache.offset - (W - 1 - accepted))
        last = logits[0, accepted, :]
        return (buf, count, cache, last, done, rounds + 1)

    buf, count, cache, last, done, rounds = jax.lax.while_loop(
        cond, body,
        (buf, jnp.asarray(Tp, jnp.int32), cache, last,
         jnp.asarray(False), jnp.asarray(1, jnp.int32)))
    out = buf[:target]
    if eos_id is not None:
        # latch: everything after the first generated eos (and any
        # slot past count, if the loop stopped early) becomes eos
        pos = jnp.arange(target)
        is_eos = (out == eos_id) & (pos >= Tp)
        first = jnp.min(jnp.where(is_eos, pos, target))
        out = jnp.where((pos > first) | (pos >= count), eos_id, out)
    return out[None, :], rounds, count


def generate_speculative_fused(params: dict, cfg: LlamaConfig,
                               prompt: jax.Array, *,
                               max_new_tokens: int, lookup_n: int = 3,
                               draft_k: int = 8,
                               eos_id: int | None = None,
                               stats: dict | None = None) -> jax.Array:
    """Single-program prompt-lookup speculative decoding (batch 1,
    greedy). See ``_fused_speculative``; exactness vs ``generate`` is
    asserted under fp32 in tests (bf16 chunked numerics can resolve
    near-ties differently, as with any chunked verification)."""
    B, Tp = prompt.shape
    if B != 1:
        raise ValueError("speculative decoding is batch-1 "
                         f"(got batch {B}); batched requests amortize "
                         "weights already — use generate_fused")
    if Tp <= lookup_n:
        raise ValueError(f"prompt ({Tp}) must be longer than "
                         f"lookup_n ({lookup_n})")
    total_len = Tp + max_new_tokens + draft_k + 1
    out, rounds, count = _fused_speculative(
        params, prompt, cfg=cfg, max_new_tokens=max_new_tokens,
        lookup_n=lookup_n, draft_k=draft_k, eos_id=eos_id,
        total_len=total_len)
    if stats is not None:
        stats["model_calls"] = int(rounds)
        stats["tokens_out"] = int(count) - Tp  # < max_new if eos fired
    return out


def make_generate_step(example_params: dict, cfg: LlamaConfig, mesh, *,
                       max_new_tokens: int, total_len: int,
                       temperature: float = 0.0, top_k: int | None = None,
                       eos_id: int | None = None):
    """Sharded ``generate_fused``: one compiled SPMD program per mesh.

    Returns ``(params, prompt, key=None) -> tokens`` (a jitted SPMD
    program behind a thin argument-contract check) where params
    carry their training shardings (serve on an fsdp×tp mesh, like
    ``make_decode_step``), the prompt and result tokens are
    batch-sharded over (dp, fsdp), and the KV cache lives its whole
    life inside the program on ``cache_shardings`` — it is never
    materialized on the host. Greedy output matches the single-device
    ``generate_fused`` exactly (``tests/test_generate.py``).

    ``example_params`` is only inspected for the pytree structure.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from kubeflow_rm_tpu.parallel.sharding import (
        batch_pspec, param_shardings,
    )

    def run(params, prompt, key, pad_counts):
        return _fused_decode_loop(
            params, cfg, prompt, key, max_new_tokens=max_new_tokens,
            temperature=float(temperature), top_k=top_k, eos_id=eos_id,
            total_len=total_len,
            cache_sharding=cache_shardings(cfg, mesh),
            pad_counts=pad_counts)

    batch_rows = NamedSharding(mesh, PartitionSpec(("dp", "fsdp")))
    jitted = jax.jit(
        run,
        in_shardings=(param_shardings(example_params, mesh),
                      NamedSharding(mesh, batch_pspec(False)), None,
                      batch_rows),
        out_shardings=NamedSharding(mesh, batch_pspec(False)))

    def step(params, prompt, key=None, pad_counts=None):
        # same argument contract as generate_fused: cache must fit the
        # generation (an undersized cache would silently clamp
        # dynamic_update_slice writes into the last slot), and greedy
        # decoding works without a key
        if total_len < prompt.shape[1] + max_new_tokens:
            raise ValueError(
                f"total_len={total_len} < prompt {prompt.shape[1]} + "
                f"new {max_new_tokens}")
        if temperature > 0 and key is None:
            raise ValueError(
                "sampling (temperature > 0) requires a PRNG key")
        if pad_counts is None:
            pad_counts = jnp.zeros((prompt.shape[0],), jnp.int32)
        return jitted(params, prompt,
                      key if key is not None else jax.random.key(0),
                      pad_counts)

    return step


def generate(params: dict, cfg: LlamaConfig, prompt: jax.Array, *,
             max_new_tokens: int, key: jax.Array | None = None,
             temperature: float = 0.0, top_k: int | None = None,
             eos_id: int | None = None,
             max_len: int | None = None,
             pad_counts: jax.Array | None = None) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` (B, Tp).

    ``temperature`` 0 (default) is greedy argmax; otherwise softmax
    sampling, optionally truncated to the ``top_k`` highest logits.
    Sequences that emit ``eos_id`` keep it and then repeat it (static
    shapes — the result is (B, Tp + max_new_tokens), pad-right).

    ``pad_counts`` (B,) marks leading left-pad slots per row (the same
    ragged-batch contract as ``generate_fused``): pads are masked out
    of attention and positions shift so padded rows match unpadded
    per-row calls — needed when the serving batcher routes padded
    batches down this loop path (int4 weights, see serve_llama).
    """
    B, Tp = prompt.shape
    S = max_len or (Tp + max_new_tokens)
    if S < Tp + max_new_tokens:
        raise ValueError(
            f"max_len={S} < prompt {Tp} + new {max_new_tokens}")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")

    # params ride as a jit ARGUMENT of the shared _decode_step, never a
    # closure: captured weights would be baked into the lowered module
    # as constants (a multi-GB HLO for real models)
    cache = init_cache(cfg, B, S)
    logits, cache = _decode_step(params, cfg, cache, prompt, pad_counts)
    last = logits[:, -1, :]

    out = [prompt]
    done = jnp.zeros((B,), bool)
    for i in range(max_new_tokens):
        if key is not None:
            key, sub = jax.random.split(key)
        else:
            sub = None
        nxt = _pick(last, sub, temperature=temperature, top_k=top_k)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        out.append(nxt[:, None])
        if i + 1 < max_new_tokens:
            logits, cache = _decode_step(params, cfg, cache, nxt[:, None],
                                         pad_counts)
            last = logits[:, -1, :]
    return jnp.concatenate(out, axis=1)


@partial(jax.jit, static_argnames=("temperature", "top_k"))
def _pick_row(last, key, *, temperature, top_k):
    """Jitted ``_pick`` through the same sampling source as both batch
    decode paths: a sampling request's ``(V,)`` row under its own PRNG
    stream, or the engine's ``(slots, V)`` array, greedy, whole."""
    return _pick(last[None, :], key, temperature=temperature,
                 top_k=top_k)[0]


@partial(jax.jit, donate_argnames=("last",))
def _seat_row(last, row, i):
    """A seated request's logits ``row`` into slot ``i`` of the
    engine's ``(slots, V)`` array; ``i`` is traced: one program."""
    return last.at[i].set(row)


def _bucket_len(n: int) -> int:
    """Next power of two ≥ n: the prefill padding buckets, so a storm
    of ragged prompts compiles O(log) prefill programs instead of one
    per distinct length (same policy as serve_llama's batcher)."""
    b = 1
    while b < n:
        b *= 2
    return b


#: in-engine SLO classes, drained by weighted share at token
#: boundaries (replacing the single FIFO between gateway and engine)
SLO_CLASSES = ("interactive", "batch", "best_effort")
DEFAULT_CLASS_WEIGHTS = {"interactive": 8, "batch": 3, "best_effort": 1}


class EngineRequest:
    """Handle returned by ``ContinuousBatchingEngine.submit``:
    ``tokens`` fills in as the request decodes; ``done`` flips when the
    slot retires (eos or max_new_tokens)."""

    _next_id = 0

    def __init__(self, prompt, *, max_new_tokens, eos_id, temperature,
                 top_k, key, slo_class="interactive",
                 speculative=False):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = top_k
        self.key = key
        self.slo_class = slo_class
        # per-request execution options: ``speculative`` runs the whole
        # generation as one fused prompt-lookup program at admission
        # (batch/best_effort only); ``chain`` is a serialized KV chain
        # installed in place of prefill (models.paging export format)
        self.speculative = bool(speculative)
        self.chain = None
        self.tokens: list[int] = []
        self.done = False
        self.rid = EngineRequest._next_id
        EngineRequest._next_id += 1
        # filled by the engine: the decode-step ordinal at seating,
        # and the request's timeline on ``time.perf_counter()`` —
        # queued, taken off the queue for a slot (before its prefill
        # is dispatched), each token picked, retired
        self.admitted_step = None
        self.t_submitted = None
        self.t_admitted = None
        self.t_tokens: list[float] = []
        self.t_finished = None

    @property
    def t_first_token(self):
        return self.t_tokens[0] if self.t_tokens else None

    def timeline(self) -> dict:
        """The stamps as one dict, for whoever hands them on."""
        return {"t_submitted": self.t_submitted,
                "t_admitted": self.t_admitted,
                "t_first_token": self.t_first_token,
                "t_finished": self.t_finished,
                "t_tokens": list(self.t_tokens)}


class ContinuousBatchingEngine:
    """Slot-based continuous-batching decode engine.

    ``submit`` queues a request into its SLO class; ``step`` admits
    queued requests into free slots (one prefill each), picks every
    live slot's next token (one program over the ``(slots, V)`` logits
    and one device-to-host transfer, whatever the live slots), retires
    slots that hit eos or their token budget and runs ONE decode step
    for the rest — so short requests leave (and new ones enter)
    mid-flight instead of waiting for the longest neighbour.

    KV lives in a block pool (``models.paging``) with per-slot block
    tables, refcounted copy-on-write prefix sharing (a shared system
    prompt is prefilled once, later requests adopt the cached blocks),
    and LRU retention of retired prefix blocks.

    Admission drains three priority-weighted class queues
    (``SLO_CLASSES``) by smooth weighted round-robin at token
    boundaries — interactive requests keep jumping a best-effort
    backlog without starving it.

    Exactness contract: each request's output is
    bit-identical to ``generate_fused(prompt[None],
    max_new_tokens=..., max_len=slot_len)`` for that request alone
    (greedy; sampled requests use their own key stream) — cached
    prefix or not. Packed-int4 params are unpacked ONCE at
    construction so per-step cost is the int8→bf16 dequant prologue,
    same as the fixed fused path.

    A config with recurrent layers (``cfg.has_recurrent_state``:
    ``models.nemotron_h``) runs the same loop over a cache that keeps a
    recurrent state a slot beside the block pool of its attention
    layers (``paging.HybridPagedCache``). Such state is not addressable
    by token block, so for that family a prefix hit is not taken (the
    whole prompt is prefilled; ``prefix_hits_refused_total`` counts the
    hits passed over) and the entry points that move or rewind a chain
    of blocks raise: ``install_chain``, ``adopt_chain``,
    ``prefill_chain`` and ``speculative=True``. State snapshots at
    block boundaries would lift both (ROADMAP R7). A config with
    sliding-window layers (``models.afmoe``) is refused the same, for
    the same reason: a window layer keeps a ring of its last positions
    a slot (``paging.WindowPagedCache``), overwritten in place.
    """

    def __init__(self, params, cfg, *, slots: int = 8,
                 slot_len: int = 256, paged: bool = True,
                 block_size: int = 16, num_blocks: int | None = None,
                 class_weights: dict | None = None):
        # ``paged`` chooses nothing: the block pool is the one cache.
        # It is accepted because perf/kinds/serve.py passes
        # ``paged=sv["paged"]`` and is the next benchmark PR's to edit
        # (ROADMAP D7); the keyword goes with that key.
        if paged is not True:
            raise ValueError(
                f"paged={paged!r}: the block pool is the engine's only "
                "cache")
        if slot_len % block_size:
            raise ValueError(
                f"slot_len {slot_len} must be a multiple of "
                f"block_size {block_size}")
        self.cfg = cfg
        self.slots = slots
        self.slot_len = slot_len
        self.block_size = block_size
        # unpack int4 leaves once, outside any per-step work; a tree
        # without one is kept as it is, not copied through a program;
        # the projections the decode matmuls read (out, in) are turned
        # once here too, the other leaves shared with the caller's tree
        self.params = serving_layout(
            jax.jit(unpack_int4_params)(params) if has_int4_leaf(params)
            else params)
        self._recurrent = bool(cfg.has_recurrent_state)
        # what a slot holds beside its blocks, or None
        self._slot_state = paging.slot_state(cfg)
        # the cache lives where the weights live: a replica whose
        # params were committed to its own chip must not allocate its
        # pool on the default device (every replica of a fleet on
        # device 0)
        leaf = jax.tree_util.tree_leaves(self.params)[0]
        home = leaf.devices()
        place = (jax.default_device(next(iter(home))) if len(home) == 1
                 else contextlib.nullcontext())
        maxb = slot_len // block_size
        if num_blocks is None:
            # every slot fully packed + 50% headroom so retired
            # prefix blocks can be RETAINED instead of recycled
            num_blocks = (paging.RESERVED_BLOCKS + slots * maxb
                          + max(maxb, (slots * maxb) // 2))
        self.pool = paging.BlockPool(num_blocks, block_size)
        with place:
            self.cache = paging.init_paged_cache(
                cfg, slots, slot_len, num_blocks, block_size)
            # every slot's last logits (a step replaces them, admission
            # writes a row), committed where a step's output will be
            self._last = jax.device_put(
                np.zeros((slots, cfg.vocab_size), np.float32),
                next(iter(home)) if leaf.committed and len(home) == 1
                else None)
        self._slot_req: list[EngineRequest | None] = [None] * slots
        self._slot_blocks: list[list | None] = [None] * slots
        # the decode kernel's chunk of a strip, and a seated slot's
        # whole chunks among its first k (whole_chunks)
        self._chunk_blocks = chunk_blocks(block_size, maxb)
        self._slot_whole: list[list | None] = [None] * slots
        self._queues = {c: [] for c in SLO_CLASSES}
        self.class_weights = dict(DEFAULT_CLASS_WEIGHTS)
        if class_weights:
            self.class_weights.update(class_weights)
        self._credits = {c: 0.0 for c in SLO_CLASSES}
        # counters surfaced by stats()
        self.decode_steps = 0
        self.host_syncs_total = 0      # blocking transfers in _pick
        self.pick_programs_total = 0   # programs _pick dispatched
        self.kv_blocks_read_total = 0
        self.kv_chunks_total = 0       # chunks the decode kernel walks
        self.kv_chunks_whole_total = 0   # ... copied in one DMA each
        self.prefills = 0
        self.occupancy_sum = 0
        self.admitted_total = 0
        self.finished_total = 0
        self.admitted_by_class = {c: 0 for c in SLO_CLASSES}
        self.prefix_hit_tokens = 0
        self.prompt_tokens = 0
        self.prefix_hits_refused_total = 0
        # disaggregation + speculative counters
        self.chain_installs = 0
        self.chains_exported = 0
        self.chains_adopted = 0
        self.speculative_requests = 0
        self.speculative_model_calls = 0
        self._spec_finished: list[EngineRequest] = []
        if _jit_sentinel.enabled():
            # prompt lengths bucket to powers of two (_bucket_len), so
            # a pow-2 slot_len admits at most log2(slot_len)+1 prefill
            # shapes; decode always runs the full (slots,) batch — ONE
            # shape, ever. The sentinel turns both into assertions.
            _jit_sentinel.set_limit("engine.prefill",
                                    slot_len.bit_length())
            _jit_sentinel.set_limit("engine.decode_step", 1)
            _jit_sentinel.track("engine.prefill", paging.paged_prefill)
            _jit_sentinel.track("engine.decode_step",
                                paging.paged_decode_step)

    # -- request lifecycle -------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: int,
               eos_id: int | None = None, temperature: float = 0.0,
               top_k: int | None = None,
               key: jax.Array | None = None,
               slo_class: str = "interactive",
               speculative: bool = False) -> EngineRequest:
        Tp = len(prompt)
        if Tp == 0:
            raise ValueError("empty prompt")
        if slo_class not in SLO_CLASSES:
            raise ValueError(f"unknown slo_class {slo_class!r} "
                             f"(one of {SLO_CLASSES})")
        if speculative:
            self._refuse_slot_state("speculative decode")
            # one fused program monopolizes the device for the whole
            # generation — a latency-class request must never do that,
            # and prompt-lookup drafting is greedy by construction
            if slo_class == "interactive":
                raise ValueError(
                    "speculative decode is a batch/best_effort option "
                    "(interactive stays on the continuous-batching "
                    "path)")
            if temperature > 0:
                raise ValueError("speculative decode is greedy-only")
            if Tp <= 3:
                raise ValueError(
                    f"speculative decode needs a prompt longer than "
                    f"lookup_n=3 (got {Tp})")
        need = _bucket_len(Tp) + max_new_tokens
        if need > self.slot_len:
            raise ValueError(
                f"request needs {need} cache slots (prefill bucket "
                f"{_bucket_len(Tp)} + {max_new_tokens} new) > slot_len "
                f"{self.slot_len}")
        chunks = -(-(Tp + max_new_tokens) // self.block_size)
        if chunks > self.pool.usable_blocks:
            raise ValueError(
                f"request needs {chunks} KV blocks > pool of "
                f"{self.pool.usable_blocks} usable blocks")
        if temperature > 0 and key is None:
            raise ValueError("sampling (temperature > 0) requires a key")
        req = EngineRequest(prompt, max_new_tokens=max_new_tokens,
                            eos_id=eos_id, temperature=temperature,
                            top_k=top_k, key=key, slo_class=slo_class,
                            speculative=speculative)
        req.t_submitted = time.perf_counter()
        self._queues[slo_class].append(req)
        return req

    def install_chain(self, chain: dict, *, max_new_tokens: int,
                      eos_id: int | None = None,
                      temperature: float = 0.0,
                      top_k: int | None = None,
                      key: jax.Array | None = None,
                      slo_class: str = "interactive") -> EngineRequest:
        """Submit a request whose prefill is REPLACED by a serialized
        KV chain (``models.paging.export_chain`` format, produced by a
        prefill replica for exactly this prompt): the chain's chunks
        seat directly in the pool and sampling starts from the carried
        last-token logits — zero prefill FLOPs on this replica.
        Verification happens here, before queueing: a corrupted chunk
        raises ``ValueError`` and nothing is enqueued."""
        self._refuse_slot_state("install_chain")
        paging.verify_chain(chain)
        if int(chain["block_size"]) != self.block_size:
            raise ValueError(
                f"chain block_size {chain['block_size']} != engine "
                f"block_size {self.block_size}")
        if chain.get("tokens") is None or chain.get("last_logits") is None:
            raise ValueError("install_chain needs a full chain "
                             "(tokens + last_logits); partial chains "
                             "go through adopt_chain")
        ck = chain["chunks_k"]
        if (ck.shape[0] != self.cache.k.shape[0]
                or ck.shape[2:] != self.cache.block_shape):
            raise ValueError("chain chunk shape does not fit this "
                             "engine's cache")
        req = self.submit(chain["tokens"],
                          max_new_tokens=max_new_tokens, eos_id=eos_id,
                          temperature=temperature, top_k=top_k,
                          key=key, slo_class=slo_class)
        req.chain = chain
        return req

    def adopt_chain(self, chain: dict) -> int:
        """Seat a foreign chain in the local pool as retained prefix
        cache — no slot, no request; the next ``submit`` for a prompt
        sharing the prefix hits it like any locally-prefilled chain.
        Returns the number of chunks adopted (0 when the chain is
        already local or the pool is transiently full)."""
        self._refuse_slot_state("adopt_chain")
        keys = list(zip(chain["covers"], chain["keys"]))
        if len(self.pool.lookup_chain(keys)) == len(keys):
            return 0
        got = paging.import_chain(self.cache, self.pool, chain)
        if got is None:
            return 0
        self.cache, blocks = got
        self.pool.decref(blocks)   # retained at ref 0 until evicted
        self.chains_adopted += 1
        return len(blocks)

    def _refuse_slot_state(self, what: str) -> None:
        if self._slot_state:
            raise ValueError(
                f"{what} needs cache state addressable by token block; "
                f"{type(self.cfg).__name__} has {self._slot_state}")

    def chain_coverage(self, prompt) -> int:
        """Prompt tokens the local prefix cache already covers (for a
        config with recurrent or window layers none: a hit is not
        taken)."""
        if self._slot_state:
            return 0
        keys = paging.prefix_keys(prompt, self.block_size)
        chain = self.pool.lookup_chain(keys)
        return keys[len(chain) - 1][0] if chain else 0

    def _next_queued(self) -> EngineRequest | None:
        """Smooth weighted round-robin over the non-empty class
        queues: every pick tops each contender up by its weight, the
        highest credit wins and pays back the round's total — over
        time each class's share of admissions converges to its weight
        share, and no non-empty class starves."""
        live = [c for c in SLO_CLASSES if self._queues[c]]
        if not live:
            return None
        total = sum(self.class_weights[c] for c in live)
        for c in live:
            self._credits[c] += self.class_weights[c]
        chosen = max(live, key=lambda c: (self._credits[c],
                                          -SLO_CLASSES.index(c)))
        self._credits[chosen] -= total
        return self._queues[chosen].pop(0)

    def _requeue_front(self, req: EngineRequest) -> None:
        self._queues[req.slo_class].insert(0, req)

    def evict_queued(self) -> list[EngineRequest]:
        """Pull every not-yet-admitted request back out (drain path:
        the gateway re-routes them to another replica). Admitted
        slots are untouched — they finish here."""
        out: list[EngineRequest] = []
        for c in SLO_CLASSES:
            out.extend(self._queues[c])
            self._queues[c] = []
        return out

    def _admit(self) -> None:
        for i in range(self.slots):
            if self._slot_req[i] is not None:
                continue
            while True:
                req = self._next_queued()
                if req is None:
                    return
                t_taken = time.perf_counter()
                if req.speculative:
                    # runs whole at this boundary, never holds a slot
                    self._run_speculative(req, t_taken)
                    continue
                break
            if req.chain is not None:
                keys = paging.prefix_keys(req.prompt, self.block_size)
                if len(self.pool.lookup_chain(keys)) == len(keys):
                    # full local hit: adopt the cached blocks instead
                    # of seating duplicate chunks from the payload
                    req.chain = None
            if req.chain is not None:
                last = self._admit_chain(i, req)
            else:
                last = self._admit_paged(i, req)
            if last is None:
                # transient block OOM: head waits at the front of its
                # class queue; blocks free as slots retire (or as
                # retained prefix blocks get evicted), so this always
                # makes progress eventually
                self._requeue_front(req)
                return
            if req.chain is None:
                self.prefills += 1
            self._last = _seat_row(self._last, last, i)
            self._slot_req[i] = req
            # the strip is fixed from here to retirement
            self._slot_whole[i] = whole_chunks(
                self._slot_blocks[i], self._chunk_blocks).tolist()
            req.admitted_step = self.decode_steps
            req.t_admitted = t_taken
            self.admitted_total += 1
            self.admitted_by_class[req.slo_class] += 1

    def _run_speculative(self, req: EngineRequest,
                         t_taken: float) -> None:
        """Execute a speculative request whole: one fused prompt-lookup
        program (``generate_speculative_fused``), greedy, exactness-
        matched to ``generate_fused`` for the same prompt. The request
        finishes at this token boundary without consuming a slot."""
        stats: dict = {}
        out = generate_speculative_fused(
            self.params, self.cfg,
            jnp.asarray([req.prompt], jnp.int32),
            max_new_tokens=req.max_new_tokens, eos_id=req.eos_id,
            stats=stats)
        toks = [int(t) for t in
                jax.device_get(out)[0][len(req.prompt):]]
        if req.eos_id is not None and req.eos_id in toks:
            toks = toks[:toks.index(req.eos_id) + 1]
        req.tokens = toks
        req.done = True
        req.admitted_step = self.decode_steps
        # the fused program hands every token over at once
        req.t_admitted = t_taken
        req.t_finished = time.perf_counter()
        req.t_tokens = [req.t_finished] * len(toks)
        self.admitted_total += 1
        self.admitted_by_class[req.slo_class] += 1
        self.finished_total += 1
        self.speculative_requests += 1
        self.speculative_model_calls += stats.get("model_calls", 0)
        self._spec_finished.append(req)

    def _prefill_suffix(self, prompt, keys, needed: int):
        """Plan ``needed`` blocks for ``prompt`` and prefill what the
        pool does not hold of it. ``None`` on transient block OOM (pool
        state untouched), else ``(n_hit, shared, fresh, final_row,
        fork_src, prefill)``: the caller installs ``prefill`` (last real
        token's logits row, K, V, positions), then unpins ``fork_src``.

        Plan: the longest consecutive cached chain covers ``n_hit``
        prompt tokens (clamped to Tp-1: the last prompt token is
        always prefilled, its logits seed sampling). Chunks fully
        inside the hit are ADOPTED (incref, never written); the chunk
        containing ``n_hit`` — when mid-block — is FORKED: the request
        gets its own copy, because its own writes (suffix prefill +
        generated tokens from offset Tp) land there. That fork is the
        copy-on-write: shared blocks are immutable, first write forks.
        """
        pool, BS = self.pool, self.block_size
        maxb = self.slot_len // BS
        Tp = len(prompt)
        chain = pool.lookup_chain(keys)
        if self._slot_state and chain:
            # the blocks are there, the state that goes with them is not
            self.prefix_hits_refused_total += 1
            chain = []
        n_hit = min(keys[len(chain) - 1][0] if chain else 0, Tp - 1)
        # fit: cached tokens + the suffix's padding bucket must fit
        # the strip; dropping back to a block boundary only costs
        # re-prefill of the dropped tokens
        while n_hit > 0 and n_hit + _bucket_len(Tp - n_hit) > self.slot_len:
            n_hit = ((n_hit - 1) // BS) * BS
        shared_full = n_hit // BS
        fork = n_hit % BS != 0
        shared = chain[:shared_full]
        # pin sources before alloc: alloc may EVICT ref-0 retained
        # blocks, and evicting a block we are about to read from (or
        # re-handing it out as our own fresh block) would corrupt the
        # copy. On OOM the pins roll back — no torn state.
        pins = chain[:shared_full + 1] if fork else shared
        pool.incref(pins)
        fresh = pool.alloc(needed - shared_full)
        if fresh is None:
            pool.decref(pins)
            return None
        if fork:
            pool.cow_forks += 1
        load_row = [paging.NULL_BLOCK] * maxb
        load_row[:len(pins)] = pins
        final_row = [paging.NULL_BLOCK] * maxb
        final_row[:shared_full] = shared
        final_row[shared_full:needed] = fresh
        suffix = prompt[n_hit:]
        Tc = _bucket_len(len(suffix))
        padded = jnp.asarray([suffix + [0] * (Tc - len(suffix))],
                             jnp.int32)
        _jit_sentinel.note("engine.prefill", padded)
        with _span("engine.prefill", hot=True):
            prefill = paging.paged_prefill(
                self.params, self.cfg, self.cache,
                jnp.asarray(load_row, jnp.int32),
                jnp.asarray(n_hit, jnp.int32), padded,
                jnp.asarray(len(suffix), jnp.int32))
        return (n_hit, shared, fresh, final_row,
                chain[shared_full] if fork else None, prefill)

    def _register_chain(self, keys, final_row) -> None:
        parent = None
        for covered, key in keys:
            self.pool.register(
                key, final_row[(covered - 1) // self.block_size],
                parent=parent, covered=covered)
            parent = key

    def _admit_paged(self, i: int, req: EngineRequest):
        """Plan blocks, prefill the un-cached suffix, install. Returns
        the last real token's logits row, or ``None`` on transient
        block OOM."""
        Tp = len(req.prompt)
        keys = paging.prefix_keys(req.prompt, self.block_size)
        needed = -(-(Tp + req.max_new_tokens) // self.block_size)
        plan = self._prefill_suffix(req.prompt, keys, needed)
        if plan is None:
            return None
        n_hit, shared, fresh, final_row, fork_src, prefill = plan
        # (with recurrent layers a fifth: the state to install)
        last, tk, tv, tpos, *state = prefill
        # owned chunks land in their blocks; shared chunks and tail
        # chunks past the allocation divert to SINK (never overwrite a
        # shared block, never touch NULL)
        dest_row = [b if len(shared) <= c < needed else paging.SINK_BLOCK
                    for c, b in enumerate(final_row)]
        self.cache = paging.paged_install(
            self.cache, tk, tv, tpos, jnp.asarray(i, jnp.int32),
            jnp.asarray(final_row, jnp.int32),
            jnp.asarray(dest_row, jnp.int32),
            jnp.asarray(Tp, jnp.int32), *state)
        if fork_src is not None:
            self.pool.decref([fork_src])   # unpin the fork source
        self._register_chain(keys, final_row)
        self._slot_blocks[i] = shared + fresh
        self.prefix_hit_tokens += n_hit
        self.prompt_tokens += Tp
        return last

    def _admit_chain(self, i: int, req: EngineRequest):
        """Seat a verified foreign chain straight into slot ``i``: the
        chain's chunks land in freshly allocated blocks, counters seat
        at the real prompt length, and sampling starts from the
        carried last-token logits — the decode replica runs ZERO
        prefill FLOPs. Returns ``None`` on transient block OOM.

        Exactness: chunk contents are the prefill replica's
        ``paged_prefill`` output for this exact prompt on the same
        weights, round-tripped through host memory bit-for-bit;
        columns past the prompt carry ``_UNFILLED`` positions so the
        causal mask hides them, and decode overwrites from offset Tp
        exactly as a local admission would."""
        pool, BS = self.pool, self.block_size
        maxb = self.slot_len // BS
        chain = req.chain
        Tp, budget = len(req.prompt), req.max_new_tokens
        nchain = len(chain["keys"])
        needed = -(-(Tp + budget) // BS)
        fresh = pool.alloc(needed)
        if fresh is None:
            return None
        cache = self.cache
        idx = jnp.asarray(fresh[:nchain], jnp.int32)
        final_row = [paging.NULL_BLOCK] * maxb
        final_row[:needed] = fresh
        positions = cache.positions.at[idx].set(
            jnp.asarray(chain["chunks_pos"], jnp.int32))
        if needed > nchain:
            # decode-budget blocks past the chain may be recycled:
            # wipe their positions so the gathered strip never shows a
            # stale row (the no-stale-reads guarantee paged_install
            # provides on the prefill path)
            tail = jnp.asarray(fresh[nchain:], jnp.int32)
            positions = positions.at[tail].set(_UNFILLED)
        self.cache = paging.PagedKVCache(
            k=cache.k.at[:, idx].set(jnp.asarray(
                chain["chunks_k"], cache.k.dtype).reshape(
                    -1, nchain, *cache.k.shape[2:])),
            v=cache.v.at[:, idx].set(jnp.asarray(
                chain["chunks_v"], cache.v.dtype).reshape(
                    -1, nchain, *cache.v.shape[2:])),
            positions=positions,
            block_tables=cache.block_tables.at[i].set(
                jnp.asarray(final_row, jnp.int32)),
            write_idx=cache.write_idx.at[i].set(Tp),
            pos_next=cache.pos_next.at[i].set(Tp),
            kv_heads=cache.kv_heads,
        )
        self._register_chain(zip(chain["covers"], chain["keys"]),
                             final_row)
        self._slot_blocks[i] = fresh
        self.prefix_hit_tokens += Tp   # the whole prompt arrived cached
        self.prompt_tokens += Tp
        self.chain_installs += 1
        return np.asarray(chain["last_logits"])

    def prefill_chain(self, prompt) -> dict | None:
        """Prefill-replica entry point: compute the full prompt's KV
        chain into the local pool (adopting any cached prefix),
        register it, and export it serialized with the last real
        token's logits — so a decode replica can ``install_chain`` it
        without prefilling. No decode slot is touched; the chain stays
        behind as retained (ref-0) prefix cache, so a resumed or
        repeated prompt only prefills its new suffix. Returns ``None``
        on transient block OOM."""
        self._refuse_slot_state("prefill_chain")
        prompt = [int(t) for t in prompt]
        Tp = len(prompt)
        if Tp == 0:
            raise ValueError("empty prompt")
        if _bucket_len(Tp) > self.slot_len:
            raise ValueError(
                f"prompt bucket {_bucket_len(Tp)} > slot_len "
                f"{self.slot_len}")
        pool, BS = self.pool, self.block_size
        maxb = self.slot_len // BS
        keys = paging.prefix_keys(prompt, BS)
        needed = -(-Tp // BS)          # prompt only: no decode budget
        plan = self._prefill_suffix(prompt, keys, needed)
        if plan is None:
            return None
        n_hit, shared, fresh, final_row, fork_src, prefill = plan
        last, tk, tv, tpos = prefill
        own = slice(len(shared), needed)
        # carve owned chunks into their blocks WITHOUT seating any
        # slot table — prefill replicas never decode, the chain lives
        # purely in the pool + prefix index
        L = self.cache.k.shape[0]
        ck = tk[:, 0].reshape(L, maxb, *self.cache.k.shape[2:])
        cv = tv[:, 0].reshape(L, maxb, *self.cache.v.shape[2:])
        cp = tpos[0].reshape(maxb, BS)
        idx = jnp.asarray(fresh, jnp.int32)
        self.cache = paging.PagedKVCache(
            k=self.cache.k.at[:, idx].set(ck[:, own]),
            v=self.cache.v.at[:, idx].set(cv[:, own]),
            positions=self.cache.positions.at[idx].set(cp[own]),
            block_tables=self.cache.block_tables,
            write_idx=self.cache.write_idx,
            pos_next=self.cache.pos_next,
            kv_heads=self.cache.kv_heads,
        )
        if fork_src is not None:
            pool.decref([fork_src])
        self._register_chain(keys, final_row)
        out = paging.export_chain(self.cache, pool, prompt)
        # logits keep their compute dtype: install-side sampling must
        # see the exact values solo prefill would produce
        out["last_logits"] = np.array(last)
        out["nbytes"] += out["last_logits"].nbytes
        # release: everything drops to ref 0 — registered blocks are
        # retained as prefix cache until evicted (or promoted)
        pool.decref(shared)
        pool.decref(fresh)
        self.prefills += 1
        self.chains_exported += 1
        self.prefix_hit_tokens += n_hit
        self.prompt_tokens += Tp
        return out

    def _retire(self, i: int) -> None:
        if self._slot_blocks[i] is not None:
            self.pool.decref(self._slot_blocks[i])
        self._slot_blocks[i] = self._slot_req[i] = None

    def step(self) -> list[EngineRequest]:
        """Admit, sample, retire, decode — one token boundary. Returns
        the requests that finished at this boundary. Four spans
        partition it (recorded while a profiler session is open):
        admit, pick, dispatch, scatter; two more partition each of pick
        (fetch, walk) and dispatch (prepare, launch)."""
        with _span("engine.step"):
            with _span("engine.admit"):
                self._admit()
            with _span("engine.pick"):
                finished, tokens, active = self._pick()
            if any(active):
                with _span("engine.dispatch", hot=True):
                    last = self._dispatch(tokens, active)
                with _span("engine.scatter"):
                    self._last = last
                    self.decode_steps += 1
                    self.occupancy_sum += sum(active)
                    # what the step touched of each live slot's table:
                    # the blocks up to the one its token landed in, and
                    # the kernel's chunks over those the tokens before
                    # it fill. The host knows every length (prompt +
                    # tokens picked so far, the one fed among them) —
                    # no device sync, and counted here, while the
                    # device runs the step
                    BS, CB = self.block_size, self._chunk_blocks
                    for i, r in enumerate(self._slot_req):
                        if r is None:
                            continue
                        n = len(r.prompt) + len(r.tokens)
                        self.kv_blocks_read_total += -(-n // BS)
                        nb = -(-(n - 1) // BS)
                        self.kv_chunks_total += -(-nb // CB)
                        self.kv_chunks_whole_total += (
                            self._slot_whole[i][nb // CB])
        return finished

    def _pick(self):
        """Sample every live slot's next token from its last logits,
        retire the slots that are through. Returns the requests that
        finished and, a slot each, the token to feed and whether the
        slot stays live."""
        # the pick programs' dispatch and the one fetch; the walk after
        # it queues nothing, so the device sits idle all through it
        with _span("engine.pick.fetch"):
            # speculative requests ran whole inside _admit
            finished, self._spec_finished = self._spec_finished, []
            tokens = [0] * self.slots
            active = [False] * self.slots
            live = [(i, r) for i, r in enumerate(self._slot_req)
                    if r is not None]
            # one program for all greedy rows, one for each sampling row
            # (its request's own key stream)
            greedy, sampled = None, {}
            if any(r.temperature <= 0 for _, r in live):
                greedy = _pick_row(self._last, None, temperature=0.0,
                                   top_k=None)
            for i, req in live:
                if req.temperature > 0:
                    req.key, sub = jax.random.split(req.key)
                    sampled[i] = _pick_row(self._last[i], sub,
                                           temperature=req.temperature,
                                           top_k=req.top_k)
            if live:
                # the ONE deliberate sync per token boundary, once all
                # are dispatched: the tokens drive host-side scheduling
                # (EOS retirement, admission) and cannot stay on device.
                self.pick_programs_total += (len(sampled)
                                             + (greedy is not None))
                self.host_syncs_total += 1
                greedy, sampled = jax.device_get((greedy, sampled))
        with _span("engine.pick.walk"):
            for i, req in live:
                nxt = int(sampled[i] if i in sampled else greedy[i])
                now = time.perf_counter()
                req.tokens.append(nxt)
                req.t_tokens.append(now)
                hit_eos = req.eos_id is not None and nxt == req.eos_id
                if hit_eos or len(req.tokens) >= req.max_new_tokens:
                    req.done = True
                    req.t_finished = now
                    finished.append(req)
                    self._retire(i)
                    self.finished_total += 1
                else:
                    tokens[i] = nxt
                    active[i] = True
        return finished, tokens, active

    def _dispatch(self, tokens, active):
        """One decode step for all slots; returns the logits rows."""
        with _span("engine.dispatch.prepare"):
            tok_arr = np.asarray(tokens, np.int32)
            act_arr = np.asarray(active, bool)
            _jit_sentinel.note("engine.decode_step", tok_arr, act_arr)
        # jax's dispatch of the program, nothing else: the span ends
        # when the call returns
        with _span("engine.dispatch.launch"):
            last, self.cache = paging.paged_decode_step(
                self.params, self.cfg, self.cache, tok_arr, act_arr)
        return last

    def run(self) -> list[EngineRequest]:
        """Drive ``step`` until every queued/live request retires."""
        out: list[EngineRequest] = []
        while (self.queue_depth
               or any(r is not None for r in self._slot_req)):
            out.extend(self.step())
        return out

    # -- observability -----------------------------------------------------

    @property
    def queue_depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    @property
    def queue_depth_by_class(self) -> dict:
        return {c: len(self._queues[c]) for c in SLO_CLASSES}

    @property
    def active_slots(self) -> int:
        return sum(r is not None for r in self._slot_req)

    def device_counters(self) -> dict:
        """The counters the programs keep on the device, in the cache
        they donate (``paging.HybridPagedCache.counters``), fetched by
        ONE blocking transfer: call it at a window's two ends, never a
        step (``stats()`` does no device fetch, the gateway calls it
        every step). ``*_total`` sum decode steps and prefills,
        ``decode_*`` are the decode steps' alone; empty for a family
        whose programs keep none. With window layers two more: the
        pairs one window layer and one full layer attended, each as a
        count modulo 2^32 (a reader takes differences modulo 2^32); a
        hybrid cache that counts pairs (``models.lfm2``) has the full
        layer's alone."""
        if not hasattr(self.cache, "counters"):
            return {}
        dec, pre = np.asarray(jax.device_get(self.cache.counters),
                              np.int64)
        names = ("expert_assignments_held_total", "experts_active_total",
                 "moe_steps_total")
        names += {3: (), 4: ("full_positions_total",),
                  5: ("window_positions_total", "full_positions_total")
                  }[len(dec)]
        out = {n: int(d + p) for n, d, p in zip(names, dec, pre)}
        out.update({"decode_" + n: int(d) for n, d in zip(names, dec)})
        # the pairs' counts wrap: their residues, never a negative
        return {n: v % (1 << 32) if "positions" in n else v
                for n, v in out.items()}

    def stats(self) -> dict:
        steps = self.decode_steps
        kv = getattr(self.cache, "kv", self.cache)     # the block pool
        return {
            "slots": self.slots,
            "slot_len": self.slot_len,
            "active_slots": self.active_slots,
            "queue_depth": self.queue_depth,
            "queue_depth_by_class": self.queue_depth_by_class,
            "decode_steps": steps,
            "host_syncs_total": self.host_syncs_total,
            "pick_programs_total": self.pick_programs_total,
            "prefills": self.prefills,
            "admitted_total": self.admitted_total,
            "admitted_by_class": dict(self.admitted_by_class),
            "finished_total": self.finished_total,
            "batch_occupancy": (self.occupancy_sum / (steps * self.slots)
                                if steps else 0.0),
            "speculative_requests": self.speculative_requests,
            "speculative_model_calls": self.speculative_model_calls,
            **self.pool.stats(),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prompt_tokens,
            "prefix_hit_ratio": (
                self.prefix_hit_tokens / self.prompt_tokens
                if self.prompt_tokens else 0.0),
            "prefix_hits_refused_total": self.prefix_hits_refused_total,
            "recurrent_state_bytes": (
                sum(a.nbytes for a in (self.cache.ssm, self.cache.conv)
                    if a is not None)
                if self._recurrent else 0),
            "window_state_bytes": (
                self.cache.ring_k.nbytes + self.cache.ring_v.nbytes
                if hasattr(self.cache, "ring_k") else 0),
            "kv_pool_bytes": kv.k.nbytes + kv.v.nbytes,
            "chain_installs": self.chain_installs,
            "chains_exported": self.chains_exported,
            "chains_adopted": self.chains_adopted,
            # against decode_steps x slots x (slot_len / block_size),
            # the share of a whole-cache strip the steps still touch
            "kv_blocks_read_total": self.kv_blocks_read_total,
            # the chunks the decode kernel walks over those blocks, and
            # those it copies in one DMA for K and one for V
            "kv_chunks_total": self.kv_chunks_total,
            "kv_chunks_whole_total": self.kv_chunks_whole_total,
        }
