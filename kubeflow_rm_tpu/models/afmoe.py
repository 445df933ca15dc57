"""AFMoE-family decoder (``model_type`` ``afmoe``, Arcee's Trinity):
sliding-window and full attention layers, leading dense layers, then
sigmoid-routed experts beside a shared one.

Each layer is two sub-layers, each normed before and after (sandwich
norms): ``x <- x + N2(attn(N1(x)))``, ``x <- x + N4(ffn(N3(x)))``,
every ``N`` a plain RMSNorm ``x / rms(x) * w``. The embedding is
multiplied by ``sqrt(dim)`` (``mup_enabled``); a final norm, then an
untied head. The trunk (``decode._run_hybrid_blocks``) sees a layer as
two parts, so ``pattern`` has two characters a layer: ``W`` or ``F``,
then ``D`` or ``E``.

- ``W`` — window attention (``window_attention``): q, k, v; RMSNorm
  over q and k a head; RoPE (theta ``rope_theta``, split halves) on
  the whole head; causal softmax over the ``sliding_window`` newest
  keys, the query's own among them (query ``p`` reads ``p -
  sliding_window + 1 .. p``), grouped-query, scale ``head_dim ** -0.5``;
  ``(o * sigmoid(h W_gate)) W_o``. Its cache is a ring of the last
  window a slot (``paging.WindowPagedCache``).
- ``F`` — full attention (``full_attention``): the same without any
  positional encoding (NoPE) and without a window; its cache is the
  block pool.
- ``D`` — a dense SwiGLU MLP, ``W_down (silu(W_gate x) * W_up x)``, in
  the first ``num_dense_layers`` layers.
- ``E`` — sparse experts (``sparse_moe``): ``s = sigmoid(x W_r)`` over
  ``n_routed_experts`` in float32, the ``top_k`` largest of ``s +
  expert_bias`` chosen, their unbiased ``s`` renormalised to sum to 1
  and scaled by ``route_scale``; SwiGLU experts of ``expert_dim``; one
  shared SwiGLU expert of ``shared_dim``, ungated. ``experts_held`` =
  (first, count) says which of the router's experts this chip holds
  (``parallel.moe.held_experts_ffn``): what the others would add is
  left out, and the shares of all chips add up to the whole layer, the
  shared expert counted once.

Parameters are stacked per kind (``blocks_w``, ``blocks_f``,
``blocks_d``, ``blocks_e``), each layer of a stack drawn from its own
key; the expert matrices are a list with an array a layer (a slice of
a stacked operand is copied before a custom call). ``wq``, ``wk`` and
``wv`` are kept (out, in), as the published weights are and as the
matmul that feeds the norms, rope and cache reads them, and a layer
each too: sliced out of a stack, all the layers' (out, in) ``wq`` were
copied out every decode step (268 MB at the published widths,
compiled for a v5e). ``vocab_size`` is the rows of embedding and head
held here.

This module is imported where its config is built (the benchmark's
``perf/kinds/serve_afmoe.py``, the tests), never by the package; its
``init_params`` is called from here, not through
``models.init_params``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.models.decode import _UNFILLED, _run_hybrid_blocks
from kubeflow_rm_tpu.models.llama import init_leaf
from kubeflow_rm_tpu.ops import (
    apply_rope, dot_product_attention, rms_norm, rope_angles,
)
from kubeflow_rm_tpu.parallel.moe import held_experts_ffn

F32 = jnp.float32
#: leaves kept in float32 whatever ``param_dtype`` says: the router and
#: its bias (a choice among a hundred close scores)
_F32_LEAVES = ("router", "expert_bias")
#: what writes into the residual stream (scaled down at init, the
#: families' shared rule)
_RESIDUAL_LEAVES = ("wo", "w_down", "moe_down", "ws_down")
WINDOW, FULL = "sliding_attention", "full_attention"


def _project(h, w, dtype):
    """``h @ w.T`` for a weight kept (out, in)."""
    return jnp.einsum("btd,od->bto", h, w.astype(dtype))


@dataclass(frozen=True)
class AfmoeConfig:
    #: a layer each, as the published ``layer_types``
    layer_types: tuple = (WINDOW, WINDOW, WINDOW, FULL) * 4
    num_dense_layers: int = 2
    vocab_size: int = 200192
    dim: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    dense_dim: int = 6144
    n_routed_experts: int = 128          # the router's width
    experts_held: tuple = (0, 128)       # (first, count) held here
    top_k: int = 8
    expert_dim: int = 1024
    shared_dim: int = 1024
    route_scale: float = 2.826
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types: {WINDOW} or {FULL} a layer")
        first, held = self.experts_held
        if not (0 <= first and held >= 1
                and first + held <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_routed_experts}")
        if not self.n_attention_layers:
            raise ValueError("no full attention layer for the block pool")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def pattern(self) -> str:
        """Two parts a layer: the attention (``W`` or ``F``), then the
        feed-forward (``D`` or ``E``)."""
        return "".join(
            ("W" if t == WINDOW else "F")
            + ("D" if i < self.num_dense_layers else "E")
            for i, t in enumerate(self.layer_types))

    @property
    def has_recurrent_state(self) -> bool:
        return False

    @property
    def n_attention_layers(self) -> int:
        """Layers whose keys and values live in the block pool."""
        return self.pattern.count("F")

    @property
    def n_window_layers(self) -> int:
        return self.pattern.count("W")

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.dim)

    def hybrid_parts(self) -> dict:
        """``decode._run_hybrid_blocks``' parts: a pattern character's
        (stack, role, function)."""
        return {"W": ("blocks_w", "window", window_attention),
                "F": ("blocks_f", "attention", full_attention),
                "D": ("blocks_d", "mlp", dense_mlp),
                "E": ("blocks_e", "experts", sparse_moe)}

    def norm(self, x, gain):
        return rms_norm(x, gain, self.norm_eps)

    @staticmethod
    def tiny(**overrides) -> "AfmoeConfig":
        """Test-sized: a dense layer, then experts; window and full
        layers in the published 3:1 ratio, a window of 8; every expert
        held."""
        return replace(
            AfmoeConfig(
                layer_types=(WINDOW, WINDOW, WINDOW, FULL), vocab_size=256,
                num_dense_layers=1, dim=64, n_heads=4, n_kv_heads=2,
                head_dim=16, sliding_window=8, dense_dim=96,
                n_routed_experts=16, experts_held=(0, 16), top_k=3,
                expert_dim=24, shared_dim=24, dtype=F32),
            **overrides)


def param_spec_shapes(cfg: AfmoeConfig) -> dict:
    """Abstract shapes of the parameter pytree, stacked per kind (a
    list holds a leaf that is kept a layer each)."""
    D, V = cfg.dim, cfg.vocab_size
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    held, F = cfg.experts_held[1], cfg.expert_dim
    Lw, Lf, Ld, Le = (cfg.pattern.count(c) for c in "WFDE")

    def attention(n):
        # (out, in), a layer each: see the module docstring
        return {"norm": (n, D), "post_norm": (n, D),
                "wq": [(H * hd, D)] * n, "wk": [(KVH * hd, D)] * n,
                "wv": [(KVH * hd, D)] * n, "q_norm": (n, hd),
                "k_norm": (n, hd), "w_gate": (n, D, H * hd),
                "wo": (n, H * hd, D)}

    shapes = {
        "embed": {"tokens": (V, D)},
        "norm_f": (D,),
        "lm_head": (D, V),
        "blocks_f": attention(Lf),
    }
    if Lw:
        shapes["blocks_w"] = attention(Lw)
    if Ld:
        shapes["blocks_d"] = {
            "norm": (Ld, D), "post_norm": (Ld, D),
            "w_gate": (Ld, D, cfg.dense_dim), "w_up": (Ld, D, cfg.dense_dim),
            "w_down": (Ld, cfg.dense_dim, D)}
    if Le:
        shapes["blocks_e"] = {
            "norm": (Le, D), "post_norm": (Le, D),
            "router": (Le, D, cfg.n_routed_experts),
            "expert_bias": (Le, cfg.n_routed_experts),
            # a layer each, not stacked: see the module docstring
            "moe_gate": [(held, D, F)] * Le,
            "moe_up": [(held, D, F)] * Le,
            "moe_down": [(held, F, D)] * Le,
            "ws_gate": (Le, D, cfg.shared_dim),
            "ws_up": (Le, D, cfg.shared_dim),
            "ws_down": (Le, cfg.shared_dim, D),
        }
    return shapes


def _init_leaf(cfg: AfmoeConfig, name: str, shape, k: jax.Array):
    """One layer's leaf by the families' shared rule
    (``llama.init_leaf``): gains 1, matrices normal with deviation
    0.02, the residual writers 0.02 / sqrt(2 L); the router's bias
    drawn as a matrix is (``models.nemotron_h``'s rule), float32."""
    rule = ("wo" if name in _RESIDUAL_LEAVES else
            name if "norm" in name else "w")
    leaf = init_leaf(cfg, rule, shape, k)
    return leaf.astype(F32) if name in _F32_LEAVES else leaf


def init_params(cfg: AfmoeConfig, key: jax.Array) -> dict:
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


def _attention(cfg: AfmoeConfig, layer: dict, h: jax.Array,
               positions: jax.Array | None):
    """q (B, T, H, hd), k and v (B, T, KVH, hd), normed and, where
    ``positions`` are given, rotated; and the part's ``finish``: the
    attention's output gated by ``sigmoid(h W_gate)`` and projected
    out."""
    B, T, _ = h.shape
    H, KVH, hd, cdt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    q = _project(h, layer["wq"], cdt).reshape(B, T, H, hd)
    k = _project(h, layer["wk"], cdt).reshape(B, T, KVH, hd)
    v = _project(h, layer["wv"], cdt).reshape(B, T, KVH, hd)
    q, k = cfg.norm(q, layer["q_norm"]), cfg.norm(k, layer["k_norm"])
    if positions is not None:
        # a masked column's position is _UNFILLED: rotate it by 0 (its
        # key is masked out by that position anyway)
        cos, sin = rope_angles(
            jnp.where(positions == _UNFILLED, 0, positions), hd,
            cfg.rope_theta)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    gate = h @ layer["w_gate"].astype(cdt)

    def finish(attn):
        gated = attn * jax.nn.sigmoid(gate)
        return gated.astype(cdt) @ layer["wo"].astype(cdt)

    return q, k, v, finish


def window_attention(cfg, layer, h, positions):
    """The window layers' attention: rotated by position."""
    return _attention(cfg, layer, h, positions)


def full_attention(cfg, layer, h, positions):
    """The full layers' attention: no positional encoding."""
    del positions
    return _attention(cfg, layer, h, None)


def dense_mlp(cfg: AfmoeConfig, layer: dict, h: jax.Array):
    cdt = cfg.dtype
    a = (jax.nn.silu(h @ layer["w_gate"].astype(cdt))
         * (h @ layer["w_up"].astype(cdt)))
    return a @ layer["w_down"].astype(cdt)


def sparse_moe(cfg: AfmoeConfig, layer: dict, h: jax.Array,
               live: jax.Array | None = None):
    """The sparse-expert part over ``h`` (B, T, D): the held experts'
    share of the routed sum plus the shared expert. ``live`` (B, T)
    marks the columns that are tokens; the others reach no expert.
    Returns the (B, T, D) output and int32 (assignments held, held
    experts with a token)."""
    B, T, D = h.shape
    cdt = cfg.dtype
    hf = h.reshape(B * T, D)
    routed, counts = held_experts_ffn(
        hf, layer["router"], layer["expert_bias"], layer["moe_up"],
        layer["moe_down"], cfg.experts_held[0], cfg.top_k, cfg.route_scale,
        live=None if live is None else live.reshape(B * T),
        gate=layer["moe_gate"])
    a = (jax.nn.silu(hf @ layer["ws_gate"].astype(cdt))
         * (hf @ layer["ws_up"].astype(cdt)))
    shared = a @ layer["ws_down"].astype(cdt)
    return (routed + shared).reshape(B, T, D), counts


def forward(params: dict, tokens: jax.Array, cfg: AfmoeConfig) -> jax.Array:
    """Full-sequence causal forward, no cache: (B, T) ids -> (B, T,
    vocab) float32 logits. For tests; no training path is built on
    it."""
    B, T = tokens.shape

    def attend(q, k, v, _layer):
        return dot_product_attention(q, k, v, causal=True), None

    def attend_window(q, k, v, _layer):
        return dot_product_attention(q, k, v, causal=True,
                                     window=cfg.sliding_window), None

    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, _state, _ys, _counts = _run_hybrid_blocks(
        params, cfg, tokens, jnp.ones((B, T), bool), positions, None, None,
        attend, attend_window)
    return logits


__all__ = ["AfmoeConfig", "dense_mlp", "forward", "full_attention",
           "init_params", "param_spec_shapes", "sparse_moe",
           "window_attention"]
