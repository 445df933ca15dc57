"""The cached decode trunk of the Llama family.

What every cached decode path is built on, and nothing that schedules
requests: the static-shape ``KVCache``, ``decode_chunk`` (prefill and
decode against it), and ``_run_blocks``, the embed / layer-scan / head
trunk whose ``attend`` each cache format supplies. ``models.paging``
(the block pool) and ``models.generate`` (the generation loops and the
serving engine) both import from here; this module imports neither.

- **Static cache** (B, max_len, KVH, hd) per layer, stacked on a
  leading layer axis like the weights, updated with
  ``lax.dynamic_update_slice`` — one compiled step serves the whole
  generation, prefill included (prefill is just a wider chunk).
- **Position-masked attention**: unfilled cache slots carry position
  ``INT32_MAX``, so the standard ``pos_q >= pos_kv`` causal mask of
  ``ops.dot_product_attention`` excludes them — no second mask path to
  keep in sync with training.
- **Layer scan**: the cache rides ``lax.scan`` as scanned xs/ys over
  the same stacked-parameter layout training uses, so compile time
  stays depth-independent. (The paged decode step is the exception:
  its pool stays outside the scan and is read through the block
  table, ``ops/paged_attention.py``.)
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.models.llama import LlamaConfig
from kubeflow_rm_tpu.models.lora import LORA_A, lora_proj
from kubeflow_rm_tpu.models.quantize import maybe_dequant
from kubeflow_rm_tpu.ops import (
    apply_rope,
    dot_product_attention,
    rms_norm,
    rope_angles,
)

_UNFILLED = jnp.iinfo(jnp.int32).max

#: the projections the serving engine keeps (out, in), each as a leaf
#: ``<name>_out_in`` (L, out, in) in place of ``<name>`` (L, in, out):
#: stored (in, out), XLA re-lays the layer's slice (out, in) before the
#: matmul, every layer of every step, for what rope, the attention and
#: the cache ask of q, k and v (``copy bf16[1,4096,4096]`` for ``wq`` at
#: Mistral's widths; ``wv``'s in prefill only). ``wo`` and the FFN are
#: read in place either way
OUT_IN_LEAVES = ("wq", "wk", "wv")
OUT_IN = "_out_in"


def serving_layout(params: dict) -> dict:
    """``params`` with each of ``OUT_IN_LEAVES`` in ``blocks`` kept
    (out, in) under its own name, for ``_run_blocks``; the tree itself
    where there is none to turn. A quantized leaf (a dict) and a leaf
    with LoRA adapters stay as they are, as does every other leaf (the
    same arrays, not copies)."""
    blocks = params.get("blocks", {})
    turn = [name for name in OUT_IN_LEAVES
            if name in blocks and not isinstance(blocks[name], dict)
            and name + LORA_A not in blocks]
    if not turn:
        return params
    blocks = dict(blocks)
    for name in turn:
        blocks[name + OUT_IN] = jnp.swapaxes(blocks.pop(name), -1, -2)
    return dict(params, blocks=blocks)


def _proj(layer: dict, name: str, h: jax.Array, *, alpha: float,
          dtype) -> jax.Array:
    """``lora_proj``, or ``h`` contracted with the last axis of the
    leaf kept (out, in) where the layer holds one."""
    w = layer.get(name + OUT_IN)
    if w is None:
        return lora_proj(layer, name, h, alpha=alpha, dtype=dtype)
    return jnp.einsum("btd,od->bto", h, w.astype(dtype))


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    k: jax.Array          # (L, B, S, KVH, hd) compute dtype
    v: jax.Array          # (L, B, S, KVH, hd)
    positions: jax.Array  # (B, S) int32; _UNFILLED marks empty slots
    offset: jax.Array     # () int32: next write index


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> KVCache:
    L, KVH, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((L, batch, max_len, KVH, hd), cfg.dtype),
        v=jnp.zeros((L, batch, max_len, KVH, hd), cfg.dtype),
        positions=jnp.full((batch, max_len), _UNFILLED, jnp.int32),
        offset=jnp.zeros((), jnp.int32),
    )


def decode_chunk(params: dict, cfg: LlamaConfig, cache: KVCache,
                 tokens: jax.Array,
                 pad_counts: jax.Array | None = None,
                 ) -> tuple[jax.Array, KVCache]:
    """Run ``tokens`` (B, Tc) through the model at the cache offset.

    One function serves prefill (Tc = prompt length) and decode
    (Tc = 1). Returns (logits (B, Tc, V) fp32, updated cache). The
    chunk must fit: offset + Tc <= cache length.

    ``pad_counts`` (B,) enables ragged batches under static shapes —
    the serving path's requirement: row *i*'s first ``pad_counts[i]``
    slots are left-padding. Pad slots get position ``_UNFILLED``, so
    the standard causal mask excludes them from every later query
    (their garbage K/V is invisible), and real tokens' positions are
    shifted down so each row's first real token sits at position 0 —
    batched left-padded output is bit-identical to running each row
    unpadded (``tests/test_generate.py``).
    """
    B, Tc = tokens.shape

    positions = cache.offset + jnp.arange(Tc, dtype=jnp.int32)
    positions = jnp.broadcast_to(positions, (B, Tc))
    if pad_counts is not None:
        positions = positions - pad_counts[:, None]
        positions = jnp.where(positions < 0, _UNFILLED, positions)
    kv_positions = jax.lax.dynamic_update_slice(
        cache.positions, positions, (0, cache.offset))

    def write_kv(c, val):
        return jax.lax.dynamic_update_slice(c, val, (0, cache.offset, 0, 0))

    logits, (new_k, new_v) = _run_blocks(
        params, cfg, tokens, positions, (cache.k, cache.v),
        _cache_attend(write_kv, positions, kv_positions))
    new_cache = KVCache(k=new_k, v=new_v, positions=kv_positions,
                       offset=cache.offset + Tc)
    return logits, new_cache


def _cache_attend(write_kv, positions, kv_positions):
    """The ``attend`` of the two callers whose cache rides the layer
    scan (``decode_chunk``, ``paging.paged_prefill``): the layer's
    ``(ck, cv)`` strips come in as the scanned value, this chunk's K/V
    lands in them through ``write_kv`` (a ``dynamic_update_slice`` at
    the chunk's offset), the chunk attends over the whole strip under
    the position mask, and the written strips go out as the layer's
    scan output."""
    def attend(q, k, v, strips):
        ck, cv = strips
        ck = write_kv(ck, k)
        cv = write_kv(cv, v)
        attn = dot_product_attention(
            q, ck, cv, causal=True,
            positions_q=positions, positions_kv=kv_positions,
        )
        return attn, (ck, cv)
    return attend


def _run_blocks(params, cfg, tokens, positions, layer_xs, attend):
    """Transformer trunk shared by every cached decode path: embed,
    layer scan (attention against the KV cache + FFN), final norm, lm
    head. The callers differ ONLY in how positions are assigned and in
    ``attend(q, k, v, xs) -> (attn, ys)``, which lands this chunk's
    K/V (B, Tc, KVH, hd) in the layer's cache and attends ``q``
    (B, Tc, H, hd) over it. ``layer_xs`` is scanned beside the layer
    weights and handed to ``attend`` a layer at a time: the cache
    strips themselves for the callers of ``_cache_attend``, the
    layer's index for ``paged_decode_step``, which reads the pool
    through the block table. The math around it is identical, which
    is what makes the continuous-batching engine bit-identical to
    ``generate_fused``. A projection the tree holds (out, in)
    (``serving_layout``, the engine's tree) is contracted on its last
    axis, one held (in, out) as trained on its first. Returns (logits,
    the stacked ``ys``)."""
    B, Tc = tokens.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.dtype

    # rope of a ~2^31 position is finite but wild; clamp pads to 0
    # (their K is masked out by the _UNFILLED position anyway)
    rope_pos = jnp.where(positions == _UNFILLED, 0, positions)
    cos, sin = rope_angles(rope_pos, hd, cfg.rope_theta)

    x = params["embed"]["tokens"][tokens].astype(cdt)

    # family dispatch for the FFN half: dense SwiGLU or expert mixture
    # (the router aux loss is a training quantity — discarded at decode)
    from kubeflow_rm_tpu.models.mixtral import MixtralConfig

    if isinstance(cfg, MixtralConfig):
        from kubeflow_rm_tpu.parallel.moe import moe_ffn

        def ffn(layer, h):
            dq = {k: (maybe_dequant(v, cdt) if k.startswith("moe") else v)
                  for k, v in layer.items()}
            out, _aux = moe_ffn(dq, h, cfg.moe, dtype=cdt)
            return out
    else:
        def ffn(layer, h):
            proj = partial(lora_proj, layer, alpha=cfg.lora_alpha,
                           dtype=cdt)
            gate = proj("w_gate", h)
            up = proj("w_up", h)
            return proj("w_down", jax.nn.silu(gate) * up)

    def body(x, scanned):
        layer, xs = scanned
        proj = partial(_proj, layer, alpha=cfg.lora_alpha, dtype=cdt)
        h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
        q = proj("wq", h).reshape(B, Tc, H, hd)
        k = proj("wk", h).reshape(B, Tc, KVH, hd)
        v = proj("wv", h).reshape(B, Tc, KVH, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn, ys = attend(q, k, v, xs)
        x = x + proj("wo", attn.reshape(B, Tc, H * hd))
        x = x + ffn(layer, rms_norm(x, layer["mlp_norm"], cfg.norm_eps))
        return x, ys

    x, ys = jax.lax.scan(body, x, (params["blocks"], layer_xs))
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = (x @ maybe_dequant(params["lm_head"], cdt)
              ).astype(jnp.float32)
    return logits, ys


def _run_hybrid_blocks(params, cfg, tokens, mask, positions, ssm, conv,
                       attend, attend_window=None, head_at=None,
                       routes=None):
    """The trunk of a config whose layers are parts of several kinds
    (``models.nemotron_h``, ``models.qwen3_next``, ``models.afmoe``,
    ``models.lfm2``),
    beside ``_run_blocks`` and for the same callers: embed, the
    pattern's parts in order, each ``x <- x + part(norm(x))``, final
    norm, head.

    The config names its parts: ``cfg.hybrid_parts()`` maps each
    character of ``cfg.pattern`` to (the stack of its parameters, its
    role, its function), and ``cfg.norm(x, gain)`` is the family's
    pre-norm. A part whose layer holds a ``post_norm`` gain is normed
    after as well (``x <- x + norm'(part(norm(x)))``, sandwich norms),
    a config with an ``embed_scale`` multiplies the embedding by it,
    and a tree with no ``lm_head`` reads the embedding's rows as the
    head (tied). Five roles:

    - ``"recurrent"``: ``fn(cfg, layer, h, ssm, conv, i, mask) -> (out,
      ssm', conv')`` advances the ``i``-th recurrent layer's state, a
      row at a time, in the whole stacked arrays (``ssm`` (Lr, B, ...)
      float32, or None for a family whose only state is the tail,
      ``conv`` (Lr, B, kernel - 1, channels));
    - ``"experts"``: ``fn(cfg, layer, h, mask) -> (out, (assignments
      held, held experts with a token))``, or with a third, the experts
      each column chose (B, Tc, top_k), which a list ``routes`` gathers
      in pattern order (a family that logs its choices, ``models.lfm2``);
    - ``"attention"``: ``fn(cfg, layer, h, positions) -> (q, k, v,
      finish)``; ``attend(q, k, v, i) -> (attn, ys)`` lands the
      ``i``-th attention layer's K/V and attends, as ``_run_blocks``'
      does, and ``finish(attn (B, Tc, H hd))`` is the part's output;
    - ``"window"``: the same, through ``attend_window`` and its own
      count of layers: attention over a sliding window, whose cache is
      not the block pool;
    - ``"mlp"``: ``fn(cfg, layer, h) -> out``, a dense feed-forward.

    A Python loop over the pattern, not a scan: the kinds differ in
    weights, state and work, one period (what a chip of the benchmark's
    deployments holds) has nothing that repeats, and a static layer
    index lets each stacked leaf be read where it lies. ``mask``
    (B, Tc) marks the real columns (a right-padded bucket's prefix; at
    decode the live rows): the others advance no state and reach no
    expert. ``positions`` (B, Tc) are the columns' (``_UNFILLED`` where
    masked), for a family whose attention rotates by position.
    ``head_at`` (a traced column index) applies the final norm and the
    head to that column alone, the logits then (B, 1, vocab): a long
    prompt's prefill wants its last real column's, and the head over
    every column of an 8,192 bucket is a (8192, vocab) product.
    Returns (logits, (ssm', conv'), the list of ``ys`` (both attention
    roles', in pattern order), int32 (expert assignments held, held
    experts with a token, 1))."""
    B, Tc = tokens.shape
    cdt = cfg.dtype
    parts = cfg.hybrid_parts()
    x = params["embed"]["tokens"][tokens].astype(cdt)
    scale = getattr(cfg, "embed_scale", None)
    if scale is not None:
        x = x * scale
    at = dict.fromkeys(parts, 0)
    ys = []
    counts = jnp.zeros((2,), jnp.int32)
    for kind in cfg.pattern:
        stack, role, part = parts[kind]
        i = at[kind]
        at[kind] += 1
        layer = {k: v[i] for k, v in params[stack].items()}
        h = cfg.norm(x, layer["norm"])
        if role == "recurrent":
            out, ssm, conv = part(cfg, layer, h, ssm, conv, i, mask)
        elif role == "experts":
            out, held, *chosen = part(cfg, layer, h, mask)
            counts = counts + jnp.stack(held)
            if routes is not None:
                routes.append(chosen[0])
        elif role == "mlp":
            out = part(cfg, layer, h)
        else:
            q, k, v, finish = part(cfg, layer, h, positions)
            attn, y = (attend_window if role == "window" else attend)(
                q, k, v, i)
            ys.append(y)
            out = finish(attn.reshape(B, Tc, -1))
        if "post_norm" in layer:
            out = cfg.norm(out, layer["post_norm"])
        x = x + out
    if head_at is not None:
        x = jax.lax.dynamic_slice_in_dim(x, head_at, 1, axis=1)
    x = cfg.norm(x, params["norm_f"])
    if "lm_head" in params:
        logits = (x @ params["lm_head"].astype(cdt)).astype(jnp.float32)
    else:                       # the head tied to the embedding
        logits = jnp.einsum("btd,vd->btv", x,
                            params["embed"]["tokens"].astype(cdt)
                            ).astype(jnp.float32)
    counts = jnp.concatenate([counts, jnp.ones((1,), jnp.int32)])
    return logits, (ssm, conv), ys, counts
