"""Qwen3-Next-family hybrid decoder: Gated DeltaNet and gated attention
mixers, softmax-routed SwiGLU experts after every mixer.

Each of ``n_layers`` layers is ``h <- h + mixer(N1(h)); h <- h +
moe(N2(h))``; the mixer of layer ``i`` is the linear one unless
``(i + 1) % full_attention_interval == 0``. ``N`` is a zero-centred
RMSNorm, ``x / rms(x) * (1 + w)``, its gains stored as published
(around 0). The trunk (``decode._run_hybrid_blocks``) sees a layer as
two parts, so ``pattern`` has two characters a layer: ``L`` or ``F``,
then ``E``.

- ``L`` — Gated DeltaNet (``gated_delta_mix``): ``[q k v z] = x
  W_qkvz^T`` (``W_qkvz`` kept (out, in) as published), the outputs
  grouped a key head at a time as the published modelling code splits
  them (q, k, then the head's value heads' v and z); ``[b a] = x W_ba`` grouped likewise; ``[q|k|v]`` through a causal
  depthwise convolution without bias and SiLU; q and k repeated to the
  value heads (value head ``h`` reads key head ``h // r``), L2-normed,
  q scaled by ``dk ** -0.5``; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``; the gated delta rule (``ops.gated_delta``)
  on a (dk, dv) float32 state a value head; ``y = rmsnorm(o) w
  silu(z)`` a head, plain gain; ``y W_o``. A slot carries the state
  and the convolution's last ``conv_kernel - 1`` columns.
- ``F`` — gated attention (``gated_attention``): ``[q | gate] = x
  W_q^T`` (kept (out, in)) interleaved a head, k and v; zero-centred RMSNorm over q and k
  a head; RoPE (theta ``rope_theta``, split halves) on the first
  ``rotary_dim`` dimensions; causal softmax attention, grouped-query;
  ``(o * sigmoid(gate)) W_o``.
- ``E`` — sparse experts (``sparse_moe``): ``softmax(x W_r)`` over
  ``n_routed_experts`` in float32, the ``top_k`` largest renormalised
  to sum to 1; each expert ``W_2 (silu(W_g x) * W_u x)``; the shared
  expert of the same shape scaled by ``sigmoid(x w_sg)``. ``experts_held``
  = (first, count) says which of the router's experts this chip holds
  (``parallel.moe.held_experts_ffn``): what the others would add is
  left out, and the shares of all chips add up to the whole layer, the
  shared expert counted once.

Parameters are stacked per kind as ``models.nemotron_h``'s are
(``blocks_l``, ``blocks_f``, ``blocks_e``), each layer of a stack drawn
from its own key; the expert matrices are a list with an array a layer
(a slice of a stacked operand is copied before a custom call), and so
is ``W_qkvz`` (from a stack, XLA copies all its layers' slices out
every decode step, 150 MB), each drawn as the stack's layer was.
``vocab_size`` is the rows of embedding and head held here. The
multi-token-prediction layer of the published model is not built.

This module is imported where its config is built (the benchmark's
``perf/kinds/serve_qwen3_next.py``, the tests), never by the package:
what the other families load at start-up does not grow. Its
``init_params`` is called from here, not through ``models.init_params``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.models.decode import _UNFILLED, _run_hybrid_blocks
from kubeflow_rm_tpu.ops import (
    apply_rope, dot_product_attention, gated_delta, rms_norm, rope_angles,
)
from kubeflow_rm_tpu.parallel.moe import held_experts_ffn

F32 = jnp.float32
#: leaves kept in float32 whatever ``param_dtype`` says: the router and
#: the recurrence's own
_F32_LEAVES = ("router", "A_log", "dt_bias")
#: what writes into the residual stream (scaled down at init)
_RESIDUAL_LEAVES = ("out_proj", "wo", "moe_down", "ws_down")
#: zero-centred gains, stored around 0 (``1 + w`` multiplies)
_CENTRED_NORMS = ("norm", "q_norm", "k_norm", "norm_f")
#: the two projections kept (out, in), as the published ``nn.Linear``
#: weights are: stored (in, out) like the other matrices, XLA lays a
#: layer's slice out transposed before the matmul every step (0.54 ms
#: of a 3.49 ms decode program on the v5e, PERF.md section 5, PR 42)
_PUBLISHED_LAYOUT = ("w_qkvz", "wq")


def _project(h, w, dtype):
    """``h @ w.T`` for a weight kept (out, in)."""
    return jnp.einsum("btd,od->bto", h, w.astype(dtype))


@dataclass(frozen=True)
class Qwen3NextConfig:
    n_layers: int = 4
    full_attention_interval: int = 4
    vocab_size: int = 151936
    dim: int = 2048
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    chunk_size: int = gated_delta.CHUNK
    n_routed_experts: int = 512          # the router's width
    experts_held: tuple = (0, 512)       # (first, count) held here
    top_k: int = 10
    expert_dim: int = 512
    shared_dim: int = 512
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        first, held = self.experts_held
        if not (0 <= first and held >= 1
                and first + held <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_routed_experts}")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError("value heads must be a multiple of key heads")
        if not self.n_attention_layers:
            raise ValueError(f"{self.n_layers} layers hold no full "
                             "attention layer for the block pool")

    @property
    def pattern(self) -> str:
        """Two parts a layer: the mixer (``L`` or ``F``), then ``E``."""
        return "".join(
            ("F" if (i + 1) % self.full_attention_interval == 0 else "L")
            + "E" for i in range(self.n_layers))

    @property
    def has_recurrent_state(self) -> bool:
        return "L" in self.pattern

    @property
    def n_attention_layers(self) -> int:
        return self.pattern.count("F")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_key_heads * self.linear_key_dim

    @property
    def value_dim(self) -> int:
        return self.linear_value_heads * self.linear_value_dim

    @property
    def conv_dim(self) -> int:
        """The convolution's channels, ``[q | k | v]``."""
        return 2 * self.key_dim + self.value_dim

    def recurrent_shapes(self, slots: int) -> tuple[tuple, tuple]:
        """The cache's delta-rule state (float32) and convolution tail
        (compute dtype) for ``slots`` slots."""
        Ll = self.pattern.count("L")
        return ((Ll, slots, self.linear_value_heads, self.linear_key_dim,
                 self.linear_value_dim),
                (Ll, slots, self.conv_kernel - 1, self.conv_dim))

    def hybrid_parts(self) -> dict:
        """``decode._run_hybrid_blocks``' parts: a pattern character's
        (stack, role, function)."""
        return {"L": ("blocks_l", "recurrent", gated_delta_mix),
                "F": ("blocks_f", "attention", gated_attention),
                "E": ("blocks_e", "experts", sparse_moe)}

    def norm(self, x, gain):
        """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
        return rms_norm(x, 1.0 + gain.astype(F32), self.norm_eps)

    @staticmethod
    def tiny(**overrides) -> "Qwen3NextConfig":
        """Test-sized: one period (three linear layers and a full one),
        every expert held."""
        return replace(
            Qwen3NextConfig(
                n_layers=4, vocab_size=256, dim=64, n_heads=4,
                n_kv_heads=2, head_dim=32, linear_key_heads=2,
                linear_value_heads=4, linear_key_dim=16,
                linear_value_dim=8, chunk_size=8, n_routed_experts=16,
                experts_held=(0, 16), top_k=3, expert_dim=24,
                shared_dim=40, dtype=F32),
            **overrides)


def param_spec_shapes(cfg: Qwen3NextConfig) -> dict:
    """Abstract shapes of the parameter pytree, stacked per kind (a
    list holds a leaf that is kept a layer each)."""
    D, V = cfg.dim, cfg.vocab_size
    Ll, Lf, Le = (cfg.pattern.count(c) for c in "LFE")
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    held, F = cfg.experts_held[1], cfg.expert_dim
    return {
        "embed": {"tokens": (V, D)},
        "norm_f": (D,),
        "lm_head": (D, V),
        "blocks_l": {
            "norm": (Ll, D),
            # (out, in) as published: a key head's [q | k | v of its
            # value heads | z of them] (see _PUBLISHED_LAYOUT); a layer
            # each: see the module docstring
            "w_qkvz": [(2 * cfg.key_dim + 2 * cfg.value_dim, D)] * Ll,
            "w_ba": (Ll, D, 2 * Hv),                  # [b | a] a key head
            "conv_w": (Ll, cfg.conv_kernel, cfg.conv_dim),
            "dt_bias": (Ll, Hv),
            "A_log": (Ll, Hv),
            "gate_norm": (Ll, cfg.linear_value_dim),
            "out_proj": (Ll, cfg.value_dim, D),
        },
        "blocks_f": {
            "norm": (Lf, D),
            "wq": (Lf, H * 2 * hd, D),                # [q | gate] a head
            "wk": (Lf, D, KVH * hd),
            "wv": (Lf, D, KVH * hd),
            "q_norm": (Lf, hd),
            "k_norm": (Lf, hd),
            "wo": (Lf, H * hd, D),
        },
        "blocks_e": {
            "norm": (Le, D),
            "router": (Le, D, cfg.n_routed_experts),
            # a layer each, not stacked: see the module docstring
            "moe_gate": [(held, D, F)] * Le,
            "moe_up": [(held, D, F)] * Le,
            "moe_down": [(held, F, D)] * Le,
            "ws_gate": (Le, D, cfg.shared_dim),
            "ws_up": (Le, D, cfg.shared_dim),
            "ws_down": (Le, cfg.shared_dim, D),
            "shared_gate": (Le, D, 1),
        },
    }


def _init_leaf(cfg: Qwen3NextConfig, name: str, shape, k: jax.Array):
    """One layer's leaf. Zero-centred gains 0, the gated norm's plain
    gain 1; ``A`` uniform in [1, 16], the step ``softplus(dt_bias)``
    log-uniform in [0.001, 0.1] (Mamba-2's rule, as ``nemotron_h``'s:
    the published code's ``dt_bias`` of 1 leaves ``e^g`` under 0.3 for
    most heads, a state that forgets within a few tokens); convolution
    taps of deviation ``conv_kernel ** -0.5``; every other matrix
    normal with deviation 0.02, the residual writers 0.02 / sqrt(2 L)."""
    if name in _CENTRED_NORMS:
        return jnp.zeros(shape, cfg.param_dtype)
    if name == "gate_norm":
        return jnp.ones(shape, cfg.param_dtype)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * jax.random.uniform(k, shape))
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape))
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus's inverse
    scale = 0.02
    if name == "conv_w":
        scale = cfg.conv_kernel ** -0.5
    elif name in _RESIDUAL_LEAVES:
        scale = 0.02 / (2.0 * cfg.n_layers) ** 0.5
    leaf = jax.random.normal(k, shape) * scale
    return leaf.astype(F32 if name in _F32_LEAVES else cfg.param_dtype)


def init_params(cfg: Qwen3NextConfig, key: jax.Array) -> dict:
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
# the three kinds of part
# ---------------------------------------------------------------------------


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_delta_mix(cfg: Qwen3NextConfig, layer: dict, h: jax.Array,
                    ssm: jax.Array, conv: jax.Array, i: int,
                    mask: jax.Array):
    """The ``i``-th Gated DeltaNet mixer over ``h`` (B, T, D), from its
    state in ``ssm`` (Ll, B, Hv, dk, dv) float32 and its tail in
    ``conv`` (Ll, B, conv_kernel - 1, conv_dim). Returns ``(y (B, T,
    D), ssm', conv')``, the whole arrays.

    ``mask`` (B, T) marks the real columns, a prefix of each row: a
    masked column takes ``g = beta = 0``, which leaves the state as it
    was, and the tail is cut behind the last real column. Two shapes
    of use: a chunk (T > 1: ``gated_delta.chunked`` under the named
    scope ``gated_delta_chunked``) and a single column (T = 1:
    ``gated_delta.decode_step``, the kernel on the chip, which walks
    the live rows only)."""
    B, T, _ = h.shape
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv, r = cfg.linear_key_dim, cfg.linear_value_dim, Hv // Hk
    K, cd, cdt = cfg.conv_kernel, cfg.conv_dim, cfg.dtype
    qkvz = _project(h, layer["w_qkvz"], cdt).reshape(
        B, T, Hk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (h @ layer["w_ba"].astype(cdt)).reshape(B, T, Hk, 2 * r)
    b, a = ba[..., :r].reshape(B, T, Hv), ba[..., r:].reshape(B, T, Hv)

    mixed = jnp.concatenate([q.reshape(B, T, -1), k.reshape(B, T, -1),
                             v.reshape(B, T, -1)], axis=-1)
    seq = jnp.concatenate([conv[i].astype(cdt), mixed], axis=1)
    w = layer["conv_w"].astype(F32)
    mixed = jax.nn.silu(sum(seq[:, j:j + T].astype(F32) * w[j]
                            for j in range(K)))
    n_real = jnp.sum(mask, axis=1, dtype=jnp.int32)
    conv = conv.at[i].set(jax.vmap(
        lambda s, n: jax.lax.dynamic_slice(s, (n, 0), (K - 1, cd))
    )(seq, n_real).astype(conv.dtype))

    q = mixed[..., :cfg.key_dim].reshape(B, T, Hk, dk)
    k = mixed[..., cfg.key_dim:2 * cfg.key_dim].reshape(B, T, Hk, dk)
    v = mixed[..., 2 * cfg.key_dim:].reshape(B, T, Hv, dv)
    q = jnp.repeat(_l2norm(q), r, axis=2) * dk ** -0.5
    k = jnp.repeat(_l2norm(k), r, axis=2)
    beta = jnp.where(mask[..., None], jax.nn.sigmoid(b.astype(F32)), 0.0)
    g = jnp.where(mask[..., None], -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        a.astype(F32) + layer["dt_bias"]), 0.0)
    if T == 1:
        o, ssm = gated_delta.decode_step(ssm, i, q[:, 0], k[:, 0], v[:, 0],
                                         g[:, 0], beta[:, 0], mask[:, 0])
        o = o[:, None]
    else:
        with jax.named_scope("gated_delta_chunked"):
            o, s_i = gated_delta.chunked(q, k, v, g, beta, ssm[i],
                                         cfg.chunk_size)
        ssm = ssm.at[i].set(s_i)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    y = (o * layer["gate_norm"].astype(F32)
         * jax.nn.silu(z.reshape(B, T, Hv, dv).astype(F32))).astype(cdt)
    return y.reshape(B, T, -1) @ layer["out_proj"].astype(cdt), ssm, conv


def gated_attention(cfg: Qwen3NextConfig, layer: dict, h: jax.Array,
                    positions: jax.Array):
    """q (B, T, H, hd), k and v (B, T, KVH, hd) of the gated attention,
    normed and rotated, and the part's ``finish``: the attention's
    output gated by ``sigmoid(gate)`` and projected out."""
    B, T, _ = h.shape
    H, KVH, hd, cdt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    qg = _project(h, layer["wq"], cdt).reshape(B, T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ layer["wk"].astype(cdt)).reshape(B, T, KVH, hd)
    v = (h @ layer["wv"].astype(cdt)).reshape(B, T, KVH, hd)
    q, k = cfg.norm(q, layer["q_norm"]), cfg.norm(k, layer["k_norm"])
    # a masked column's position is _UNFILLED: rotate it by 0 (its key
    # is masked out by that position anyway)
    rot = cfg.rotary_dim
    cos, sin = rope_angles(jnp.where(positions == _UNFILLED, 0, positions),
                           rot, cfg.rope_theta)
    q = jnp.concatenate([apply_rope(q[..., :rot], cos, sin), q[..., rot:]],
                        -1)
    k = jnp.concatenate([apply_rope(k[..., :rot], cos, sin), k[..., rot:]],
                        -1)

    def finish(attn):
        gated = attn * jax.nn.sigmoid(gate.reshape(B, T, H * hd))
        return gated.astype(cdt) @ layer["wo"].astype(cdt)

    return q, k, v, finish


def sparse_moe(cfg: Qwen3NextConfig, layer: dict, h: jax.Array,
               live: jax.Array | None = None):
    """The sparse-expert part over ``h`` (B, T, D): the held experts'
    share of the routed sum plus the gated shared expert. ``live``
    (B, T) marks the columns that are tokens; the others reach no
    expert. Returns the (B, T, D) output and int32 (assignments held,
    held experts with a token)."""
    B, T, D = h.shape
    cdt = cfg.dtype
    hf = h.reshape(B * T, D)
    routed, counts = held_experts_ffn(
        hf, layer["router"], None, layer["moe_up"], layer["moe_down"],
        cfg.experts_held[0], cfg.top_k, 1.0,
        live=None if live is None else live.reshape(B * T),
        gate=layer["moe_gate"], routing="softmax")
    a = (jax.nn.silu(hf @ layer["ws_gate"].astype(cdt))
         * (hf @ layer["ws_up"].astype(cdt)))
    shared = (a @ layer["ws_down"].astype(cdt)) * jax.nn.sigmoid(
        hf @ layer["shared_gate"].astype(cdt))
    return (routed + shared).reshape(B, T, D), counts


def forward(params: dict, tokens: jax.Array,
            cfg: Qwen3NextConfig) -> jax.Array:
    """Full-sequence causal forward, no cache: (B, T) ids -> (B, T,
    vocab) float32 logits. For tests; no training path is built on
    it."""
    B, T = tokens.shape
    state, tail = cfg.recurrent_shapes(B)

    def attend(q, k, v, _layer):
        return dot_product_attention(q, k, v, causal=True), None

    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, _state, _ys, _counts = _run_hybrid_blocks(
        params, cfg, tokens, jnp.ones((B, T), bool), positions,
        jnp.zeros(state, F32), jnp.zeros(tail, cfg.dtype), attend)
    return logits


__all__ = ["Qwen3NextConfig", "forward", "gated_attention",
           "gated_delta_mix", "init_params", "param_spec_shapes",
           "sparse_moe"]
