"""The plain reference of the ``nemotron_h`` family: its forward pass
in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, no kernels, no cache, no chunked scan, no grouped matmul,
no batching of requests. It imports nothing of the program and takes
nothing the program made: its weights come from the seed by the
initialisation rule written out below, its inputs from the benchmark's
own traffic generator.

Architecture (``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``
``config.json``, ``model_type`` ``nemotron_h``). Every layer is
``x <- x + part(rmsnorm(x))`` with one part, named by a character of
``hybrid_override_pattern``; final RMSNorm, untied head.

- ``M`` (Mamba-2): ``[z | xBC | dt] = W_in h`` (d_inner | d_inner +
  2 groups x state | heads); ``xBC <- silu(causal depthwise
  conv(xBC) + b)``; ``[x | B | C]``; ``dt <- softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head; head ``p`` (group ``p // (heads /
  groups)``): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
  S_t C_t + D x_t``; ``y <- rmsnorm_per_group(y * silu(z)) * g``;
  ``W_out y``. Written as the recurrence: a scan over positions.
- ``*``: q, k, v projections, causal softmax attention, query head
  ``h`` reading key/value head ``h // (H / KVH)``, scale ``head_dim **
  -0.5``, ``W_o``.
- ``E``: ``s = sigmoid(W_r h)``; the ``num_experts_per_tok`` largest of
  ``s + b`` are chosen (``n_group`` 1: no group limit); weights ``w_i =
  s_i / (sum of the chosen s + 1e-20) x routed_scaling_factor``; ``u =
  W_f1 h`` (latent); routed ``r = sum_i w_i W_down_i relu(W_up_i
  u)^2``; out ``W_f2 r + W_sd relu(W_su h)^2``.

Departures from the published description, each also a key of the
configuration file's ``assumed`` or ``omitted``:

- attention takes **no rotary embedding** (the config carries
  ``rope_theta``; the family as published applies none);
- the router and the shared expert read the hidden state, the routed
  experts the latent one, nothing between projection and experts;
- **a share of the experts**: the chip holds experts ``first .. first
  + held`` of the router's ``router_width``; the sum over ``i`` runs
  over the chosen experts that are held, the others' terms are left
  out (the program does the same; the four shares add up to the whole
  layer because ``W_f2`` is linear);
- the vocabulary is the rows held (``vocab_size``);
- the multi-token-prediction layer is not built.

Forced by memory, none changing a value: a layer's weights are made
from the seed when the layer runs and freed after it (an ``E`` layer
is 3.0 GB in float32), attention runs one query head at a time, the
experts one at a time over every row (a row an expert was not chosen
for has weight 0 there).

``quant="int8"`` is the control: every matmul operand is rounded to
eight bits (symmetric, scaled along the contracted axis) before it is
multiplied — the precision just below the bfloat16 the configuration
states. The recurrence's own elementwise arithmetic stays float32.

Two more controls plant a fault in the routed experts' path alone, at
full precision, to show what of that path the comparison sees
(``FAULTS``): ``"routed_dropped"`` leaves the routed sum out of every
``E`` layer (the shared expert alone answers), ``"experts_shifted"``
gives the experts held the assignments of the next share's
(``first + held .. first + 2 held``), as a wrong ``experts_held_first``
would.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -2.0 ** 30
F32 = jnp.float32

#: leaf names a kind, in the order the keys are split (sorted)
KIND_LEAVES = {
    "*": ("norm", "wk", "wo", "wq", "wv"),
    "E": ("moe_down", "moe_up", "norm", "router", "router_bias", "w_f1",
          "w_f2", "ws_down", "ws_up"),
    "M": ("A_log", "D", "conv_b", "conv_w", "dt_bias", "gate_norm",
          "in_proj", "norm", "out_proj"),
}
#: the program's stacks, in the sorted order of its tree
STACKS = (("blocks_a", "*"), ("blocks_e", "E"), ("blocks_m", "M"))
TOP_LEAVES = ("embed/tokens", "lm_head", "norm_f")
KEPT_F32 = ("router", "router_bias", "A_log", "dt_bias", "D")
RESIDUAL = ("out_proj", "wo", "w_f2", "ws_down")
#: controls that are a planted fault of the routed path, not a precision
FAULTS = ("routed_dropped", "experts_shifted")


def dims_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file."""
    return {
        "pattern": config["hybrid_override_pattern"],
        "D": config["hidden_size"], "V": config["vocab_size"],
        "H": config["num_attention_heads"],
        "KVH": config["num_key_value_heads"], "hd": config["head_dim"],
        "Hm": config["mamba_num_heads"], "P": config["mamba_head_dim"],
        "G": config["n_groups"], "N": config["ssm_state_size"],
        "K": config["conv_kernel"],
        "held": config["n_routed_experts"],
        "router": config["router_width"],
        "first": config["experts_held_first"],
        "top_k": config["num_experts_per_tok"],
        "scale": float(config["routed_scaling_factor"]),
        "latent": config["moe_latent_size"],
        "F": config["moe_intermediate_size"],
        "Fs": config["moe_shared_expert_intermediate_size"],
        "eps": float(config["layer_norm_epsilon"]),
    }


def leaf_names(d: dict) -> list[str]:
    """Every leaf the program's tree has, in its flattened order."""
    names = [f"{stack}/{leaf}" for stack, kind in STACKS
             if kind in d["pattern"] for leaf in KIND_LEAVES[kind]]
    return names + list(TOP_LEAVES)


def layer_shapes(d: dict, kind: str) -> dict:
    """One layer's leaves of ``kind``."""
    D, di = d["D"], d["Hm"] * d["P"]
    cd = di + 2 * d["G"] * d["N"]
    if kind == "M":
        return {"norm": (D,), "in_proj": (D, di + cd + d["Hm"]),
                "conv_w": (d["K"], cd), "conv_b": (cd,),
                "dt_bias": (d["Hm"],), "A_log": (d["Hm"],),
                "D": (d["Hm"],), "gate_norm": (di,), "out_proj": (di, D)}
    if kind == "E":
        return {"norm": (D,), "router": (D, d["router"]),
                "router_bias": (d["router"],), "w_f1": (D, d["latent"]),
                "w_f2": (d["latent"], D),
                "moe_up": (d["held"], d["latent"], d["F"]),
                "moe_down": (d["held"], d["F"], d["latent"]),
                "ws_up": (D, d["Fs"]), "ws_down": (d["Fs"], D)}
    return {"norm": (D,), "wq": (D, d["H"] * d["hd"]),
            "wk": (D, d["KVH"] * d["hd"]), "wv": (D, d["KVH"] * d["hd"]),
            "wo": (d["H"] * d["hd"], D)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, name, shape, n_layers, dtype):
    """The initialisation rule, one leaf of one layer. Gains 1; ``A``
    uniform in [1, 16] (kept as its logarithm); the step log-uniform in
    [0.001, 0.1], floor 1e-4, kept through softplus's inverse; ``D`` 1;
    convolution taps normal with deviation ``K ** -0.5``; every other
    leaf normal with deviation 0.02, the four that write into the
    residual stream (``out_proj``, ``wo``, ``ws_down``, ``w_f2``) 0.02 /
    sqrt(2 L). The router's leaves and the recurrence's stay float32,
    the rest are stored in ``dtype``."""
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * jax.random.uniform(key, shape))
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.maximum(
            jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape)), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "D":
        return jnp.ones(shape, F32)
    scale = 0.02
    if name == "conv_w":
        scale = shape[0] ** -0.5
    elif name in RESIDUAL:
        scale = 0.02 / (2.0 * n_layers) ** 0.5
    leaf = (jax.random.normal(key, shape) * scale).astype(dtype)
    return leaf.astype(F32) if name in KEPT_F32 else leaf


def init_weights(d: dict, seed: int, dtype) -> dict:
    """A handle, not the weights: the seed and the dtype they are
    stored in. ``layer_weights`` and ``top_weights`` make a layer's
    leaves when it runs. One key a leaf, split from ``key(seed)`` in
    the order of ``leaf_names``; within a leaf one key a layer of its
    kind, split again: the program draws a stack's layers each from
    its own key, so a layer is made here without the others."""
    return {"seed": int(seed), "dtype": jnp.dtype(dtype)}


def _leaf_key(weights: dict, d: dict, name: str):
    names = leaf_names(d)
    keys = jax.random.split(jax.random.key(weights["seed"]), len(names))
    return keys[names.index(name)]


def layer_weights(weights: dict, d: dict, at: int) -> dict:
    """The leaves of layer ``at`` of the pattern."""
    kind = d["pattern"][at]
    i = d["pattern"][:at].count(kind)
    stack = next(s for s, k in STACKS if k == kind)
    out = {}
    for name, shape in layer_shapes(d, kind).items():
        k = jax.random.split(_leaf_key(weights, d, f"{stack}/{name}"),
                             d["pattern"].count(kind))[i]
        out[name] = _draw(k, name, shape, len(d["pattern"]),
                          weights["dtype"])
    return out


def top_weights(weights: dict, d: dict, name: str):
    shape = {"embed/tokens": (d["V"], d["D"]), "lm_head": (d["D"], d["V"]),
             "norm_f": (d["D"],)}[name]
    return _draw(_leaf_key(weights, d, name), name.split("/")[-1], shape,
                 len(d["pattern"]), weights["dtype"])


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

def _qdq(x, axis):
    """Round to eight bits along ``axis`` (the contracted one), keep
    float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant):
    """x (..., K) @ w (K, N)."""
    if quant == "int8":
        x, w = _qdq(x, -1), _qdq(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _mamba(h, w, d, quant):
    B, T, _ = h.shape
    Hm, P, G, N, K = d["Hm"], d["P"], d["G"], d["N"], d["K"]
    di = Hm * P
    cd = di + 2 * G * N
    zxbcdt = _mm(h, w["in_proj"], quant)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
                  zxbcdt[..., di + cd:])
    # causal depthwise convolution: column t reads t-K+1 .. t
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + T] * w["conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(xbc + w["conv_b"])
    x = xbc[..., :di].reshape(B, T, Hm, P)
    Bm = jnp.repeat(xbc[..., di:di + G * N].reshape(B, T, G, N),
                    Hm // G, axis=2)
    Cm = jnp.repeat(xbc[..., di + G * N:].reshape(B, T, G, N),
                    Hm // G, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])              # (B, T, Hm)
    A = -jnp.exp(w["A_log"])

    def step(S, col):
        x_t, b_t, c_t, dt_t = col       # (B,Hm,P) (B,Hm,N) (B,Hm,N) (B,Hm)
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return S, jnp.sum(S * c_t[:, :, None, :], -1)

    _, y = jax.lax.scan(
        step, jnp.zeros((B, Hm, P, N), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x      # (B, T, Hm, P)
    y = y.reshape(B, T, di) * jax.nn.silu(z)
    y = y.reshape(B, T, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + d["eps"])
    return _mm(y.reshape(B, T, di) * w["gate_norm"], w["out_proj"], quant)


def _attention(h, w, d, quant):
    """Causal, one query head at a time, its scores never kept."""
    B, T, _ = h.shape
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    q = _mm(h, w["wq"], quant).reshape(B, T, H, hd)
    k = _mm(h, w["wk"], quant).reshape(B, T, KVH, hd)
    v = _mm(h, w["wv"], quant).reshape(B, T, KVH, hd)
    keep = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def one(args):
        qh, kh, vh = args                                   # (B, T, hd)
        if quant == "int8":
            qh, kh = _qdq(qh, -1), _qdq(kh, -1)
        s = jnp.einsum("bqd,bsd->bqs", qh * hd ** -0.5, kh,
                       precision="highest")
        p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
        if quant == "int8":
            p, vh = _qdq(p, -1), _qdq(vh, 1)
        return jnp.einsum("bqs,bsd->bqd", p, vh, precision="highest")

    kv_of = jnp.arange(H) // (H // KVH)
    out = jax.lax.map(one, (jnp.moveaxis(q, 2, 0),
                            jnp.moveaxis(k, 2, 0)[kv_of],
                            jnp.moveaxis(v, 2, 0)[kv_of]))
    return _mm(jnp.moveaxis(out, 0, 2).reshape(B, T, H * hd), w["wo"],
               quant)


def _experts(h, w, d, quant):
    fault, quant = (quant, None) if quant in FAULTS else (None, quant)
    B, T, D = h.shape
    hf = h.reshape(B * T, D)
    s = jax.nn.sigmoid(_mm(hf, w["router"], quant))
    _, idx = jax.lax.top_k(s + w["router_bias"], d["top_k"])
    chosen = jnp.take_along_axis(s, idx, -1)
    wts = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * d["scale"]
    # (rows, router width): a row's weight for each expert, 0 if unchosen
    dense = jnp.zeros_like(s).at[jnp.arange(hf.shape[0])[:, None],
                                 idx].set(wts)
    first = d["first"] + (d["held"] if fault == "experts_shifted" else 0)
    mine = dense[:, first:first + d["held"]]
    if fault == "routed_dropped":
        mine = jnp.zeros_like(mine)
    u = _mm(hf, w["w_f1"], quant)

    def one(acc, e):
        up, down, w_e = e
        a = jnp.square(jax.nn.relu(_mm(u, up, quant)))
        return acc + w_e[:, None] * _mm(a, down, quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (w["moe_up"], w["moe_down"], mine.T))
    shared = jnp.square(jax.nn.relu(_mm(hf, w["ws_up"], quant)))
    out = _mm(routed, w["w_f2"], quant) + _mm(shared, w["ws_down"], quant)
    return out.reshape(B, T, D)


PARTS = {"M": _mamba, "*": _attention, "E": _experts}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, w, kind, dk, quant):
    d = dict(dk)
    w = {k: a.astype(F32) for k, a in w.items()}
    return x + PARTS[kind](_rms(x, w["norm"], d["eps"]), w, d, quant)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, gain, head, eps, quant):
    return _mm(_rms(x, gain.astype(F32), eps), head.astype(F32), quant)


def forward_logits(weights: dict, tokens: np.ndarray, d: dict,
                   quant: str | None = None) -> jax.Array:
    """(B, T, V) float32 logits of right-padded rows ``tokens`` (B, T).
    Layer by layer: a layer's weights are made, used by one jitted
    call and freed. (Right-padding reaches no real column: every part
    is causal.)"""
    dk = tuple(sorted(d.items()))
    # a planted fault lives in the expert layers alone
    precision = None if quant in FAULTS else quant
    x = _embed(top_weights(weights, d, "embed/tokens"),
               jnp.asarray(tokens, jnp.int32))
    for at, kind in enumerate(d["pattern"]):
        x = _layer(x, layer_weights(weights, d, at), kind, dk,
                   quant if kind == "E" else precision)
    return _head(x, top_weights(weights, d, "norm_f"),
                 top_weights(weights, d, "lm_head"), d["eps"], precision)
