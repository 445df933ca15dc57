"""LFM2-MoE-family hybrid decoder (``model_type`` ``lfm2_moe``, Liquid
AI's LFM2-8B-A1B): gated short-convolution and attention layers, two
leading dense layers, then sigmoid-routed experts.

Each layer is a mixer, then a feed-forward part, each with one
pre-norm and one residual: ``h = x + mixer(N1(x))``, ``out = h +
ffn(N2(h))``, every ``N`` a plain RMSNorm ``x / rms(x) * w``. A final
norm, then the head, which is the embedding itself (tied). The trunk
(``decode._run_hybrid_blocks``) sees a layer as two parts, so
``pattern`` has two characters a layer: ``C`` or ``A``, then ``D`` or
``E``.

- ``C`` — gated short convolution (``short_conv``): ``[B | C | x] =
  h W_in`` (no bias), ``Bx = B * x``, a causal depthwise convolution of
  ``conv_kernel`` (3) taps over time, ``z_t = sum_j w_j * Bx_{t-2+j}``
  (no bias, no activation), ``y = C * z``, ``out = y W_out``. A slot's
  whole state is the last ``conv_kernel - 1`` columns of ``Bx``: the
  serving cache keeps them a slot (``paging.HybridPagedCache``, with
  no other recurrent state).
- ``A`` — attention (``attention``): q, k, v, grouped-query at head
  ``dim / n_heads`` (64); RMSNorm over q and k a head; RoPE (theta
  ``rope_theta``, split halves) over the whole head; causal softmax at
  scale ``head_dim ** -0.5``; ``W_o``. Its cache is the block pool.
- ``D`` — a dense SwiGLU MLP, ``W_down (silu(W_gate x) * W_up x)``, in
  the first ``num_dense_layers`` layers.
- ``E`` — sparse experts (``sparse_moe``): ``s = sigmoid(x W_r)`` over
  ``n_routed_experts`` in float32, the ``top_k`` largest of ``s +
  expert_bias`` chosen, their unbiased ``s`` divided by their sum plus
  ``route_eps`` and scaled by ``route_scale``; SwiGLU experts of
  ``expert_dim``; no shared expert. ``experts_held`` = (first, count)
  says which of the router's experts this chip holds
  (``parallel.moe.held_experts_ffn``): what the others would add is
  left out, and the shares of all chips add up to the whole layer.

Every matrix is a leaf a layer (a list with an array a layer), kept
(out, in) as the published ``nn.Linear`` weights are and as its matmul
reads it: a layer's slice of a stacked matrix was copied out every
decode step (``models.afmoe``). The expert matrices are kept as
``ops/expert_ffn.py`` reads them, (held, in, out), and the router (in,
out) in float32. Gains, the convolution's taps and the router's bias
are stacked per kind. ``vocab_size`` is the rows of the embedding held
here, which are the head's too.

This module is imported where its config is built (the benchmark's
``perf/kinds/serve_lfm2.py``, the tests), never by the package; its
``init_params`` is called from here, not through
``models.init_params``.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.models.decode import _UNFILLED, _run_hybrid_blocks
from kubeflow_rm_tpu.models.llama import init_leaf
from kubeflow_rm_tpu.ops import (
    apply_rope, dot_product_attention, rms_norm, rope_angles,
)
from kubeflow_rm_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk

F32 = jnp.float32
#: leaves kept in float32 whatever ``param_dtype`` says: the router and
#: its bias (a choice among close scores)
_F32_LEAVES = ("router", "expert_bias")
#: what writes into the residual stream (scaled down at init, the
#: families' shared rule)
_RESIDUAL_LEAVES = ("out_proj", "wo", "w_down", "moe_down")
CONV, ATTENTION = "conv", "full_attention"


def _project(h, w, dtype):
    """``h @ w.T`` for a weight kept (out, in)."""
    return jnp.einsum("btd,od->bto", h, w.astype(dtype))


@dataclass(frozen=True)
class Lfm2MoeConfig:
    #: a layer each, as the published ``layer_types``
    layer_types: tuple = ((CONV, CONV, ATTENTION) + (CONV, CONV, CONV,
                                                     ATTENTION) * 4
                          + (CONV, CONV, ATTENTION, CONV, CONV))
    num_dense_layers: int = 2
    vocab_size: int = 65536
    dim: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    conv_kernel: int = 3
    rope_theta: float = 1e6
    dense_dim: int = 7168
    n_routed_experts: int = 32           # the router's width
    experts_held: tuple = (0, 32)        # (first, count) held here
    top_k: int = 4
    expert_dim: int = 1792
    route_scale: float = 1.0
    #: added to the chosen scores' sum before it divides them
    route_eps: float = 1e-6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types: {CONV} or {ATTENTION} a layer")
        first, held = self.experts_held
        if not (0 <= first and held >= 1
                and first + held <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_routed_experts}")
        if self.dim % self.n_heads:
            raise ValueError(f"dim {self.dim} is no whole number of "
                             f"{self.n_heads} heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def pattern(self) -> str:
        """Two parts a layer: the mixer (``C`` or ``A``), then the
        feed-forward (``D`` or ``E``)."""
        return "".join(
            ("C" if t == CONV else "A")
            + ("D" if i < self.num_dense_layers else "E")
            for i, t in enumerate(self.layer_types))

    @property
    def has_recurrent_state(self) -> bool:
        """A convolution tail a slot, not addressable by token block."""
        return CONV in self.layer_types

    @property
    def n_attention_layers(self) -> int:
        return self.pattern.count("A")

    @property
    def counts_pairs(self) -> bool:
        """The cache's counters add the (query, key) pairs one attention
        layer attended (``paging.HybridPagedCache``)."""
        return True

    @property
    def logs_routes(self) -> bool:
        """The cache keeps the experts each column chose
        (``paging.HybridPagedCache.routes``), which a check follows."""
        return True

    def recurrent_shapes(self, slots: int) -> tuple[None, tuple]:
        """No recurrent state beside the convolution's tail (compute
        dtype), whose shape for ``slots`` slots is the second."""
        return None, (self.pattern.count("C"), slots, self.conv_kernel - 1,
                      self.dim)

    def hybrid_parts(self) -> dict:
        """``decode._run_hybrid_blocks``' parts: a pattern character's
        (stack, role, function)."""
        return {"C": ("blocks_c", "recurrent", short_conv),
                "A": ("blocks_a", "attention", attention),
                "D": ("blocks_d", "mlp", dense_mlp),
                "E": ("blocks_e", "experts", sparse_moe)}

    def norm(self, x, gain):
        return rms_norm(x, gain, self.norm_eps)

    @staticmethod
    def tiny(**overrides) -> "Lfm2MoeConfig":
        """Test-sized: a dense layer, then experts; conv and attention
        layers in the published 3:1 ratio; every expert held."""
        return replace(
            Lfm2MoeConfig(
                layer_types=(CONV, CONV, ATTENTION, CONV), vocab_size=256,
                num_dense_layers=1, dim=64, n_heads=4, n_kv_heads=2,
                dense_dim=96, n_routed_experts=16, experts_held=(0, 16),
                top_k=3, expert_dim=24, dtype=F32),
            **overrides)


def param_spec_shapes(cfg: Lfm2MoeConfig) -> dict:
    """Abstract shapes of the parameter pytree, stacked per kind (a
    list holds a leaf that is kept a layer each)."""
    D, V = cfg.dim, cfg.vocab_size
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    held, F, E = cfg.experts_held[1], cfg.expert_dim, cfg.n_routed_experts
    Lc, La, Ld, Le = (cfg.pattern.count(c) for c in "CADE")
    shapes = {"embed": {"tokens": (V, D)}, "norm_f": (D,)}
    if Lc:
        shapes["blocks_c"] = {
            "norm": (Lc, D), "in_proj": [(3 * D, D)] * Lc,   # [B | C | x]
            "conv_w": (Lc, cfg.conv_kernel, D), "out_proj": [(D, D)] * Lc}
    if La:
        shapes["blocks_a"] = {
            "norm": (La, D), "wq": [(H * hd, D)] * La,
            "wk": [(KVH * hd, D)] * La, "wv": [(KVH * hd, D)] * La,
            "q_norm": (La, hd), "k_norm": (La, hd), "wo": [(D, H * hd)] * La}
    if Ld:
        F_d = cfg.dense_dim
        shapes["blocks_d"] = {
            "norm": (Ld, D), "w_gate": [(F_d, D)] * Ld,
            "w_up": [(F_d, D)] * Ld, "w_down": [(D, F_d)] * Ld}
    if Le:
        shapes["blocks_e"] = {
            "norm": (Le, D), "router": [(D, E)] * Le,
            "expert_bias": (Le, E),
            "moe_gate": [(held, D, F)] * Le, "moe_up": [(held, D, F)] * Le,
            "moe_down": [(held, F, D)] * Le}
    return shapes


def _init_leaf(cfg: Lfm2MoeConfig, name: str, shape, k: jax.Array):
    """One layer's leaf by the families' shared rule
    (``llama.init_leaf``): gains 1, matrices normal with deviation
    0.02, the residual writers 0.02 / sqrt(2 L); the convolution's taps
    of deviation ``conv_kernel ** -0.5`` (``models.nemotron_h``'s rule);
    the router's bias drawn as a matrix is, float32."""
    if name == "conv_w":
        return (jax.random.normal(k, shape)
                * cfg.conv_kernel ** -0.5).astype(cfg.param_dtype)
    rule = ("wo" if name in _RESIDUAL_LEAVES else
            name if "norm" in name else "w")
    leaf = init_leaf(cfg, rule, shape, k)
    return leaf.astype(F32) if name in _F32_LEAVES else leaf


def init_params(cfg: Lfm2MoeConfig, key: jax.Array) -> dict:
    """Random-init the tree of ``param_spec_shapes``: one key a leaf
    (split in the tree's flattened order), and within a stacked leaf
    one key a layer (split again), each layer drawn alone."""
    shapes = param_spec_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, (tuple, list)))
    keys = jax.random.split(key, len(flat))
    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = path[-1].key
        if isinstance(shape, list):
            leaves.append([_init_leaf(cfg, name, s, kk) for s, kk in
                           zip(shape, jax.random.split(k, len(shape)))])
        elif path[0].key.startswith("blocks_"):
            leaves.append(jax.vmap(
                lambda kk, name=name, shape=shape:
                _init_leaf(cfg, name, shape[1:], kk)
            )(jax.random.split(k, shape[0])))
        else:
            leaves.append(_init_leaf(cfg, name, shape, k))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# the four kinds of part
# ---------------------------------------------------------------------------


def short_conv(cfg: Lfm2MoeConfig, layer: dict, h: jax.Array, ssm, conv,
               i: int, mask: jax.Array):
    """The ``i``-th gated short convolution over ``h`` (B, T, D), from
    its tail in ``conv`` (Lc, B, conv_kernel - 1, D), the last columns
    of ``Bx`` before this chunk (zeros before a sequence's first).
    Returns ``(out (B, T, D), ssm, conv')``: ``ssm`` is None and passes
    through, ``conv'`` the whole array with this layer's tail advanced.

    ``mask`` (B, T) marks the real columns, a prefix of each row (a
    right-padded prefill bucket; at decode a row is live or not): the
    tail is cut behind the last real column, so a row with none keeps
    its tail, and a prompt of fewer real columns than the tail carries
    the zeros before it. The convolution is causal, so no real column
    reads a pad column."""
    B, T, D = h.shape
    K, cdt = cfg.conv_kernel, cfg.dtype
    with jax.named_scope("short_conv"):
        b, c, x = jnp.split(_project(h, layer["in_proj"], cdt), 3, axis=-1)
        seq = jnp.concatenate([conv[i].astype(cdt), b * x], axis=1)
        w = layer["conv_w"].astype(F32)
        z = sum(seq[:, j:j + T].astype(F32) * w[j] for j in range(K))
        out = _project((c.astype(F32) * z).astype(cdt), layer["out_proj"],
                       cdt)
        n_real = jnp.sum(mask, axis=1, dtype=jnp.int32)
        tail = jax.vmap(
            lambda s, n: jax.lax.dynamic_slice(s, (n, 0), (K - 1, D))
        )(seq, n_real)
    return out, ssm, conv.at[i].set(tail.astype(conv.dtype))


def attention(cfg: Lfm2MoeConfig, layer: dict, h: jax.Array,
              positions: jax.Array):
    """q (B, T, H, hd), k and v (B, T, KVH, hd), normed a head and
    rotated by position; and the part's ``finish``, the output
    projection."""
    B, T, _ = h.shape
    H, KVH, hd, cdt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    q = _project(h, layer["wq"], cdt).reshape(B, T, H, hd)
    k = _project(h, layer["wk"], cdt).reshape(B, T, KVH, hd)
    v = _project(h, layer["wv"], cdt).reshape(B, T, KVH, hd)
    q, k = cfg.norm(q, layer["q_norm"]), cfg.norm(k, layer["k_norm"])
    # a masked column's position is _UNFILLED: rotate it by 0 (its key
    # is masked out by that position anyway)
    cos, sin = rope_angles(jnp.where(positions == _UNFILLED, 0, positions),
                           hd, cfg.rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

    def finish(attn):
        return _project(attn.astype(cdt), layer["wo"], cdt)

    return q, k, v, finish


def dense_mlp(cfg: Lfm2MoeConfig, layer: dict, h: jax.Array):
    cdt = cfg.dtype
    a = (jax.nn.silu(_project(h, layer["w_gate"], cdt))
         * _project(h, layer["w_up"], cdt))
    return _project(a, layer["w_down"], cdt)


def sparse_moe(cfg: Lfm2MoeConfig, layer: dict, h: jax.Array,
               live: jax.Array | None = None):
    """The held experts' share of the routed sum over ``h`` (B, T, D).
    ``live`` (B, T) marks the columns that are tokens; the others reach
    no expert. Returns the (B, T, D) output, int32 (assignments held,
    held experts with a token) and the experts each column chose among
    all the router's, (B, T, top_k) int32 (the choice
    ``held_experts_ffn`` makes, from the same operands), which the
    serving cache logs."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    routed, counts = held_experts_ffn(
        flat, layer["router"], layer["expert_bias"],
        layer["moe_up"], layer["moe_down"], cfg.experts_held[0], cfg.top_k,
        cfg.route_scale, live=None if live is None else live.reshape(B * T),
        gate=layer["moe_gate"], eps=cfg.route_eps)
    chosen, _w = route_sigmoid_topk(flat, layer["router"],
                                    layer["expert_bias"], cfg.top_k,
                                    cfg.route_scale, eps=cfg.route_eps)
    return routed.reshape(B, T, D), counts, chosen.reshape(B, T, -1)


def forward(params: dict, tokens: jax.Array,
            cfg: Lfm2MoeConfig) -> jax.Array:
    """Full-sequence causal forward, no cache: (B, T) ids -> (B, T,
    vocab) float32 logits. For tests; no training path is built on
    it."""
    B, T = tokens.shape
    _state, tail = cfg.recurrent_shapes(B)

    def attend(q, k, v, _layer):
        return dot_product_attention(q, k, v, causal=True), None

    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, _state, _ys, _counts = _run_hybrid_blocks(
        params, cfg, tokens, jnp.ones((B, T), bool), positions, None,
        jnp.zeros(tail, cfg.dtype), attend)
    return logits


__all__ = ["Lfm2MoeConfig", "attention", "dense_mlp", "forward",
           "init_params", "param_spec_shapes", "short_conv", "sparse_moe"]
