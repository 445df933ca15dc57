"""Nemotron-H-family hybrid decoder: a trunk of three kinds of layer.

Each layer is a mixer *or* a feed-forward part alone, with one
pre-norm and one residual (``x <- x + part(norm(x))``); the config's
``pattern`` says which, a character a layer:

- ``M`` — Mamba-2 (``mamba_mix``): ``[z | xBC | dt] = W_in h``, a
  causal depthwise convolution and SiLU over ``xBC = [x | B | C]``,
  then a head at a time (heads in ``n_groups`` groups that share B and
  C) the recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``; ``y`` gated by ``silu(z)``, normalised a
  group at a time, projected out. A layer carries two pieces of state
  a sequence: ``S`` (heads, head_dim, state_size) in float32 and the
  last ``conv_kernel - 1`` columns of ``xBC``. Neither is a strip of
  positions: the serving cache keeps them a slot (``models.paging``).
- ``*`` — causal softmax attention, grouped-query, **no rotary
  embedding**: position reaches the model through the Mamba layers.
- ``E`` — sparse experts in a latent space: a sigmoid router over
  ``n_routed_experts`` reads the hidden state, the ``top_k`` chosen
  experts (squared ReLU) read a ``latent_dim``-wide projection of it,
  their weighted sum is projected back; one shared expert reads the
  hidden state itself. ``experts_held`` = (first, count) says which of
  the router's experts this chip holds (``parallel.moe
  .held_experts_ffn``): the part the others would add is left out,
  and since the projection back is linear the shares of all chips add
  up to the whole layer, the shared expert counted once.

Parameters are stacked per kind on a leading axis (``blocks_m``,
``blocks_e``, ``blocks_a``), each layer of a stack drawn from its own
key so that one layer can be made without the others (the benchmark's
reference does). The expert matrices alone are a list with an array a
layer: the grouped matmul is a custom call, and XLA copies the slice
of a stacked operand before such a call (705 MB a matrix a layer a
decode step at the published widths, compiled for a v5e), where a
whole array is read in place. ``vocab_size`` is the rows of embedding and head held
here. The multi-token-prediction layer of the published model is not
built: it drafts, and the main path's logits do not pass through it.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.models.llama import init_leaf
from kubeflow_rm_tpu.parallel.moe import held_experts_ffn

F32 = jnp.float32
#: leaves kept in float32 whatever ``param_dtype`` says: the router
#: (a choice among hundreds of close scores) and the recurrence's own
_F32_LEAVES = ("router", "router_bias", "A_log", "dt_bias", "D")
#: what writes into the residual stream (scaled down at init, the
#: families' shared rule): the mixers' output projections, the shared
#: expert's and the routed path's way back from the latent space
_RESIDUAL_LEAVES = ("out_proj", "wo", "w_f2", "ws_down")


@dataclass(frozen=True)
class NemotronHConfig:
    pattern: str = "EMEMEMEMEM*"
    vocab_size: int = 32768
    dim: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_routed_experts: int = 512          # the router's width
    experts_held: tuple = (0, 512)       # (first, count) held here
    top_k: int = 22
    routed_scaling: float = 5.0
    latent_dim: int = 1024
    expert_dim: int = 2688
    shared_dim: int = 5376
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set("ME*"):
            raise ValueError(f"pattern {self.pattern!r}: a string of "
                             "M, E and *")
        first, held = self.experts_held
        if not (0 <= first and held >= 1
                and first + held <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_routed_experts}")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    @property
    def has_recurrent_state(self) -> bool:
        """State that is not addressable by token block: a prefix of
        the cache cannot be adopted, exported or rewound."""
        return "M" in self.pattern

    @staticmethod
    def tiny(**overrides) -> "NemotronHConfig":
        """Test-sized: every kind of layer, every expert held."""
        return replace(
            NemotronHConfig(
                pattern="EMEM*", vocab_size=256, dim=64, n_heads=4,
                n_kv_heads=2, head_dim=16, mamba_heads=8,
                mamba_head_dim=8, n_groups=2, state_size=16,
                chunk_size=8, n_routed_experts=16, experts_held=(0, 16),
                top_k=3, latent_dim=32, expert_dim=48, shared_dim=96,
                dtype=F32),
            **overrides)


def param_spec_shapes(cfg: NemotronHConfig) -> dict:
    """Abstract shapes of the parameter pytree, stacked per kind (a
    list holds a leaf that is kept a layer each)."""
    D, V = cfg.dim, cfg.vocab_size
    Lm, Le, La = (cfg.pattern.count(c) for c in "ME*")
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    di, cd, Hm = cfg.d_inner, cfg.conv_dim, cfg.mamba_heads
    held = cfg.experts_held[1]
    shapes = {
        "embed": {"tokens": (V, D)},
        "norm_f": (D,),
        "lm_head": (D, V),
    }
    if Lm:
        shapes["blocks_m"] = {
            "norm": (Lm, D),
            "in_proj": (Lm, D, di + cd + Hm),      # [z | xBC | dt]
            "conv_w": (Lm, cfg.conv_kernel, cd),
            "conv_b": (Lm, cd),
            "dt_bias": (Lm, Hm),
            "A_log": (Lm, Hm),
            "D": (Lm, Hm),
            "gate_norm": (Lm, di),
            "out_proj": (Lm, di, D),
        }
    if Le:
        shapes["blocks_e"] = {
            "norm": (Le, D),
            "router": (Le, D, cfg.n_routed_experts),
            "router_bias": (Le, cfg.n_routed_experts),
            "w_f1": (Le, D, cfg.latent_dim),
            "w_f2": (Le, cfg.latent_dim, D),
            # a layer each, not stacked: see the module docstring
            "moe_up": [(held, cfg.latent_dim, cfg.expert_dim)] * Le,
            "moe_down": [(held, cfg.expert_dim, cfg.latent_dim)] * Le,
            "ws_up": (Le, D, cfg.shared_dim),
            "ws_down": (Le, cfg.shared_dim, D),
        }
    if La:
        shapes["blocks_a"] = {
            "norm": (La, D),
            "wq": (La, D, H * hd),
            "wk": (La, D, KVH * hd),
            "wv": (La, D, KVH * hd),
            "wo": (La, H * hd, D),
        }
    return shapes


def _init_leaf(cfg: NemotronHConfig, name: str, shape, k: jax.Array):
    """One layer's leaf. Matrices and gains by the families' shared
    rule (``llama.init_leaf``); the recurrence's own leaves as the
    family initialises them, so that random weights keep a state with
    a memory: ``A`` in [1, 16], the step ``dt`` log-uniform in
    [0.001, 0.1] (floor 1e-4), ``D`` one, convolution taps of deviation
    ``conv_kernel ** -0.5``."""
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * jax.random.uniform(k, shape))
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.maximum(
            jnp.exp(lo + (hi - lo) * jax.random.uniform(k, shape)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus's inverse
    if name == "D":
        return jnp.ones(shape, F32)
    if name == "conv_w":
        return (jax.random.normal(k, shape)
                * cfg.conv_kernel ** -0.5).astype(cfg.param_dtype)
    rule = ("wo" if name in _RESIDUAL_LEAVES else
            name if "norm" in name else "w")
    leaf = init_leaf(cfg, rule, shape, k)
    return leaf.astype(F32) if name in _F32_LEAVES else leaf


def init_params(cfg: NemotronHConfig, key: jax.Array) -> dict:
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
# the three kinds of layer
# ---------------------------------------------------------------------------


def mamba_mix(cfg: NemotronHConfig, layer: dict, h: jax.Array,
              state: jax.Array, conv: jax.Array, mask: jax.Array):
    """One Mamba-2 mixer over ``h`` (B, T, D) from ``state`` (B, heads,
    head_dim, state_size) float32 and ``conv`` (B, conv_kernel - 1,
    conv_dim), the last columns of ``xBC`` before this chunk. Returns
    ``(y (B, T, D), state', conv')``.

    ``mask`` (B, T) marks the real columns, a prefix of each row (a
    right-padded prefill bucket; at decode a row is live or not): a
    masked column takes a step of zero, which leaves the state as it
    was, and the convolution's tail is cut behind the last real column,
    so a row with none keeps both. Two shapes of use: a chunk (T > 1:
    the scan in chunks of ``chunk_size``, quadratic inside a chunk and
    recurrent between chunks) and a single column (T = 1: the
    recurrence itself, elementwise in float32)."""
    B, T, _ = h.shape
    Hm, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                   cfg.state_size)
    K, di, cd, cdt = cfg.conv_kernel, cfg.d_inner, cfg.conv_dim, cfg.dtype
    zxbcdt = h @ layer["in_proj"].astype(cdt)
    z, xbc, dt = jnp.split(zxbcdt, [di, di + cd], axis=-1)

    seq = jnp.concatenate([conv.astype(cdt), xbc], axis=1)  # (B,T+K-1,cd)
    w = layer["conv_w"].astype(F32)
    xbc = sum(seq[:, j:j + T].astype(F32) * w[j] for j in range(K))
    xbc = jax.nn.silu(xbc + layer["conv_b"].astype(F32))
    n_real = jnp.sum(mask, axis=1, dtype=jnp.int32)
    new_conv = jax.vmap(
        lambda s, n: jax.lax.dynamic_slice(s, (n, 0), (K - 1, cd))
    )(seq, n_real).astype(conv.dtype)

    x = xbc[..., :di].reshape(B, T, G, Hm // G, P)
    Bm = xbc[..., di:di + G * N].reshape(B, T, G, N)
    Cm = xbc[..., di + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + layer["dt_bias"])
    dt = jnp.where(mask[..., None], dt, 0.0).reshape(B, T, G, Hm // G)
    a = dt * -jnp.exp(layer["A_log"]).reshape(G, Hm // G)
    state = state.reshape(B, G, Hm // G, P, N)
    if T == 1:
        xb = dt[:, 0, ..., None] * x[:, 0]                   # (B,G,R,P)
        state = (state * jnp.exp(a[:, 0])[..., None, None]
                 + xb[..., None] * Bm[:, 0, :, None, None, :])
        y = jnp.sum(state * Cm[:, 0, :, None, None, :], axis=-1)[:, None]
    else:
        y, state = _chunked_scan(x, dt, a, Bm, Cm, state, cfg.chunk_size)
    y = y + layer["D"].reshape(G, Hm // G, 1) * x
    y = y.reshape(B, T, di) * jax.nn.silu(z.astype(F32))
    y = y.reshape(B, T, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = (y.reshape(B, T, di) * layer["gate_norm"].astype(F32)).astype(cdt)
    return (y @ layer["out_proj"].astype(cdt),
            state.reshape(B, Hm, P, N), new_conv)


def _chunked_scan(x, dt, a, Bm, Cm, state, chunk):
    """The recurrence over T columns in chunks of ``chunk``: inside a
    chunk every column reads every earlier one through the decay
    between them (a (chunk, chunk) matrix a head), each chunk's
    contribution to the state is summed once, and the states entering
    the chunks come from a scan over the chunks. ``x`` (B,T,G,R,P),
    ``dt`` and ``a`` = dt A (B,T,G,R), ``Bm`` and ``Cm`` (B,T,G,N),
    ``state`` (B,G,R,P,N); all float32, matmuls at full precision (they
    are small beside the projections). T is padded to whole chunks
    with steps of zero. Returns (y (B,T,G,R,P), the final state)."""
    B, T = x.shape[:2]
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, a, Bm, Cm = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, a, Bm, Cm))
    nc = (T + pad) // Q
    x, dt, a, Bm, Cm = (v.reshape(B, nc, Q, *v.shape[2:])
                        for v in (x, dt, a, Bm, Cm))
    ein = lambda spec, *ops: jnp.einsum(spec, *ops, precision="highest")
    xb = x * dt[..., None]
    # the chunk's columns last: (B,nc,G,R,Q)
    cs = jnp.cumsum(jnp.moveaxis(a, 2, -1), axis=-1)
    # decay from column j to column i >= j of one chunk: (B,nc,G,R,i,j)
    diff = cs[..., :, None] - cs[..., None, :]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), diff,
                              -jnp.inf))
    cb = ein("bcign,bcjgn->bcgij", Cm, Bm)
    y = ein("bcgij,bcgrij,bcjgrp->bcigrp", cb, decay, xb)
    # what each chunk adds to the state, and its whole decay
    to_end = jnp.exp(cs[..., -1:] - cs)                       # (B,nc,G,R,Q)
    added = ein("bcjgn,bcgrj,bcjgrp->bcgrpn", Bm, to_end, xb)
    whole = jnp.exp(cs[..., -1])                              # (B,nc,G,R)

    def step(s, chunk_c):
        add_c, whole_c = chunk_c
        return s * whole_c[..., None, None] + add_c, s

    state, entering = jax.lax.scan(
        step, state, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    y = y + ein("bcign,cbgrpn,bcgri->bcigrp", Cm, entering, jnp.exp(cs))
    return y.reshape(B, nc * Q, *y.shape[3:])[:, :T], state


def attention_qkv(cfg: NemotronHConfig, layer: dict, h: jax.Array):
    """q (B, T, H, hd), k and v (B, T, KVH, hd); no rotary embedding."""
    B, T, _ = h.shape
    cdt = cfg.dtype
    q = (h @ layer["wq"].astype(cdt)).reshape(B, T, cfg.n_heads,
                                              cfg.head_dim)
    k = (h @ layer["wk"].astype(cdt)).reshape(B, T, cfg.n_kv_heads,
                                              cfg.head_dim)
    v = (h @ layer["wv"].astype(cdt)).reshape(B, T, cfg.n_kv_heads,
                                              cfg.head_dim)
    return q, k, v


def latent_moe(cfg: NemotronHConfig, layer: dict, h: jax.Array,
               live: jax.Array | None = None):
    """The sparse-expert part over ``h`` (B, T, D): the held experts'
    share of the routed sum in the latent space, projected back, plus
    the shared expert. ``live`` (B, T) marks the columns that are
    tokens; the others reach no expert. Returns the (B, T, D) output
    and int32 (assignments held, held experts with a token)."""
    B, T, D = h.shape
    cdt = cfg.dtype
    hf = h.reshape(B * T, D)
    routed, counts = held_experts_ffn(
        hf, layer["router"], layer["router_bias"], layer["moe_up"],
        layer["moe_down"], cfg.experts_held[0], cfg.top_k,
        cfg.routed_scaling, expert_in=hf @ layer["w_f1"].astype(cdt),
        live=None if live is None else live.reshape(B * T))
    shared = jnp.square(jax.nn.relu(hf @ layer["ws_up"].astype(cdt)))
    out = (routed @ layer["w_f2"].astype(cdt)
           + shared @ layer["ws_down"].astype(cdt))
    return out.reshape(B, T, D), counts


def forward(params: dict, tokens: jax.Array,
            cfg: NemotronHConfig) -> jax.Array:
    """Full-sequence causal forward, no cache: (B, T) ids -> (B, T,
    vocab) float32 logits. For tests and the chip's share of a
    training-style pass; no training path is built on it."""
    from kubeflow_rm_tpu.models.decode import _run_hybrid_blocks
    from kubeflow_rm_tpu.ops import dot_product_attention

    B, T = tokens.shape
    Lm = cfg.pattern.count("M")
    ssm = jnp.zeros((Lm, B, cfg.mamba_heads, cfg.mamba_head_dim,
                     cfg.state_size), F32)
    conv = jnp.zeros((Lm, B, cfg.conv_kernel - 1, cfg.conv_dim), cfg.dtype)

    def attend(q, k, v, _layer):
        return dot_product_attention(q, k, v, causal=True), None

    logits, _state, _ys, _counts = _run_hybrid_blocks(
        params, cfg, tokens, jnp.ones((B, T), bool), ssm, conv, attend)
    return logits


__all__ = ["NemotronHConfig", "attention_qkv", "forward", "init_params",
           "latent_moe", "mamba_mix", "param_spec_shapes"]
