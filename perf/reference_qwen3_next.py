"""The plain reference of the ``qwen3_next`` family: its forward pass in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
chunked form of the delta rule, no batching of requests. It imports
nothing of the program and takes nothing the program made: its weights
come from the seed by the initialisation rule written out below, its
inputs from the benchmark's own traffic generator.

Architecture (``Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json``,
``model_type`` ``qwen3_next``). Every layer is ``x <- x +
mixer(N1(x)); x <- x + moe(N2(x))`` with ``N(x) = x / rms(x) (1 +
w)`` (eps ``rms_norm_eps``); the mixer of layer ``i`` is Gated DeltaNet
unless ``(i + 1) % full_attention_interval == 0``; final ``N``, untied
head.

- Gated DeltaNet (``W_qkvz`` and ``W_q`` are kept (out, in) as the
  published weights are, the other matrices (in, out)): ``W_qkvz``'s
  rows a key head at a time ``[q (dk) | k (dk) | v (r dv) | z (r
  dv)]``, ``r`` = value heads a key head;
  ``W_ba``'s ``[b (r) | a (r)]``; ``[q | k | v]`` (all key heads' q,
  then k, then v) through a causal depthwise convolution of width
  ``linear_conv_kernel_dim``, no bias, then SiLU; q, k repeated to the
  value heads (value head ``h`` reads key head ``h // r``), ``q <-
  q / sqrt(|q|^2 + 1e-6) dk^-1/2``, ``k <- k / sqrt(|k|^2 + 1e-6)``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
  a head at a time, the recurrence ``S_t = e^{g_t} S_{t-1} + k_t
  [beta_t (v_t - e^{g_t} S_{t-1}^T k_t)]^T``, ``o_t = S_t^T q_t``,
  token by token; ``y = o / rms(o) w silu(z)`` a head (plain gain),
  ``W_o y``.
- gated attention: ``W_q``'s rows a head at a time ``[q (hd) |
  gate (hd)]``; q and k through ``N`` over ``hd``; rotary embedding on
  the first ``hd x partial_rotary_factor`` dimensions (``rotate_half``
  of that part, inverse frequencies ``theta^(-2i/rot)``); causal
  softmax, query head ``h`` reading key/value head ``h // (H / KVH)``,
  scale ``hd^-1/2``; ``W_o (o sigmoid(gate))``.
- moe: ``p = softmax(W_r x)`` over ``router_width`` experts, the
  ``num_experts_per_tok`` largest renormalised to sum to 1; expert
  ``e``: ``W_down_e (silu(W_gate_e x) W_up_e x)``; the shared expert
  of the same form times ``sigmoid(w_sg x)``.

Departures from the published description, each also a key of the
configuration file's ``assumed`` or ``omitted``:

- **a share of the experts**: the chip holds experts ``first .. first
  + held`` of the router's ``router_width``; the routed sum runs over
  the chosen experts that are held (the program does the same; the
  shares add up to the whole layer because the sum is linear);
- the vocabulary is the rows held (``vocab_size``);
- the multi-token-prediction layer is not built;
- weights are random from the seed (the rule is ``_draw``'s).

Forced by memory and time, none changing a value: a layer's weights
are made from the seed when the layer runs and freed after it (an
expert layer is 3.2 GB in float32); attention runs one query head at a
time; the routed experts run over their own rows only (the assignments
sorted by expert, a fixed tile of rows at a time, an expert's three
matrices applied to its tile: a row an expert was not chosen for is
not multiplied by it); padding columns (``lengths``) reach no expert;
the head is applied in blocks of rows, to the columns compared alone,
and yields per column the gap of a picked token, the argmax and the
largest logit, not the (rows, vocabulary) logits (9.3 GB at the
cell's size).

``quant="int8"`` is the control: every matmul operand is rounded to
eight bits (symmetric, scaled along the contracted axis) before it is
multiplied — the precision just below the bfloat16 the configuration
states. The recurrence's own elementwise arithmetic stays float32.

Planted faults, each at full precision (``FAULTS``), to show what the
comparison sees of each mechanism: ``"routed_dropped"`` leaves the
routed sum out of every expert layer, ``"experts_shifted"`` gives the
experts held the assignments of the next share's, ``"delta_dropped"``
updates the state without ``- e^{g} S^T k`` (a plain gated linear
attention), ``"gate_dropped"`` leaves the attention's output gate out.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = -2.0 ** 30
F32 = jnp.float32
#: rows of a tile of one expert's assignments
TILE = 128
#: rows the head is applied to at a time
HEAD_ROWS = 1024

#: leaf names a kind, in the order the keys are split (sorted)
KIND_LEAVES = {
    "E": ("moe_down", "moe_gate", "moe_up", "norm", "router",
          "shared_gate", "ws_down", "ws_gate", "ws_up"),
    "F": ("k_norm", "norm", "q_norm", "wk", "wo", "wq", "wv"),
    "L": ("A_log", "conv_w", "dt_bias", "gate_norm", "norm", "out_proj",
          "w_ba", "w_qkvz"),
}
#: the program's stacks, in the sorted order of its tree
STACKS = (("blocks_e", "E"), ("blocks_f", "F"), ("blocks_l", "L"))
TOP_LEAVES = ("embed/tokens", "lm_head", "norm_f")
KEPT_F32 = ("router", "A_log", "dt_bias")
RESIDUAL = ("out_proj", "wo", "moe_down", "ws_down")
CENTRED = ("norm", "q_norm", "k_norm", "norm_f")
FAULTS = ("routed_dropped", "experts_shifted", "delta_dropped",
          "gate_dropped")


def dims_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file."""
    L = config["num_hidden_layers"]
    every = config["full_attention_interval"]
    return {
        "pattern": "".join(("F" if (i + 1) % every == 0 else "L") + "E"
                           for i in range(L)),
        "L": L, "D": config["hidden_size"], "V": config["vocab_size"],
        "H": config["num_attention_heads"],
        "KVH": config["num_key_value_heads"], "hd": config["head_dim"],
        "rot": int(config["head_dim"] * config["partial_rotary_factor"]),
        "theta": float(config["rope_theta"]),
        "Hk": config["linear_num_key_heads"],
        "Hv": config["linear_num_value_heads"],
        "dk": config["linear_key_head_dim"],
        "dv": config["linear_value_head_dim"],
        "K": config["linear_conv_kernel_dim"],
        "held": config["num_experts"],
        "router": config["router_width"],
        "first": config["experts_held_first"],
        "top_k": config["num_experts_per_tok"],
        "F": config["moe_intermediate_size"],
        "Fs": config["shared_expert_intermediate_size"],
        "eps": float(config["rms_norm_eps"]),
    }


def leaf_names(d: dict) -> list[str]:
    """Every leaf the program's tree has, in its flattened order."""
    names = [f"{stack}/{leaf}" for stack, kind in STACKS
             for leaf in KIND_LEAVES[kind]]
    return names + list(TOP_LEAVES)


def layer_shapes(d: dict, kind: str) -> dict:
    """One layer's leaves of ``kind``."""
    D = d["D"]
    if kind == "L":
        key_dim, value_dim = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
        return {"norm": (D,),
                "w_qkvz": (2 * key_dim + 2 * value_dim, D),
                "w_ba": (D, 2 * d["Hv"]),
                "conv_w": (d["K"], 2 * key_dim + value_dim),
                "dt_bias": (d["Hv"],), "A_log": (d["Hv"],),
                "gate_norm": (d["dv"],), "out_proj": (value_dim, D)}
    if kind == "F":
        H, KVH, hd = d["H"], d["KVH"], d["hd"]
        return {"norm": (D,), "wq": (2 * H * hd, D), "wk": (D, KVH * hd),
                "wv": (D, KVH * hd), "q_norm": (hd,), "k_norm": (hd,),
                "wo": (H * hd, D)}
    return {"norm": (D,), "router": (D, d["router"]),
            "moe_gate": (d["held"], D, d["F"]),
            "moe_up": (d["held"], D, d["F"]),
            "moe_down": (d["held"], d["F"], D),
            "ws_gate": (D, d["Fs"]), "ws_up": (D, d["Fs"]),
            "ws_down": (d["Fs"], D), "shared_gate": (D, 1)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, name, shape, n_layers, dtype):
    """The initialisation rule, one leaf of one layer. Zero-centred
    gains (``norm``, ``q_norm``, ``k_norm``, ``norm_f``) 0, the gated
    norm's gain 1; ``A`` uniform in [1, 16] (kept as its logarithm);
    the step ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], kept
    through softplus's inverse; convolution taps normal with deviation
    ``K ** -0.5``; every other leaf normal with deviation 0.02, the
    four that write into the residual stream (``out_proj``, ``wo``,
    ``moe_down``, ``ws_down``) 0.02 / sqrt(2 L). The router's leaf and
    the recurrence's stay float32, the rest are stored in ``dtype``."""
    if name in CENTRED:
        return jnp.zeros(shape, dtype)
    if name == "gate_norm":
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * jax.random.uniform(key, shape))
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(key, shape))
        return dt + jnp.log(-jnp.expm1(-dt))
    scale = 0.02
    if name == "conv_w":
        scale = shape[0] ** -0.5
    elif name in RESIDUAL:
        scale = 0.02 / (2.0 * n_layers) ** 0.5
    leaf = jax.random.normal(key, shape) * scale
    return leaf.astype(F32 if name in KEPT_F32 else dtype)


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
    """The leaves of part ``at`` of the pattern."""
    kind = d["pattern"][at]
    i = d["pattern"][:at].count(kind)
    stack = next(s for s, k in STACKS if k == kind)
    out = {}
    for name, shape in layer_shapes(d, kind).items():
        k = jax.random.split(_leaf_key(weights, d, f"{stack}/{name}"),
                             d["pattern"].count(kind))[i]
        out[name] = _draw(k, name, shape, d["L"], weights["dtype"])
    return out


def top_weights(weights: dict, d: dict, name: str):
    shape = {"embed/tokens": (d["V"], d["D"]), "lm_head": (d["D"], d["V"]),
             "norm_f": (d["D"],)}[name]
    return _draw(_leaf_key(weights, d, name), name.split("/")[-1], shape,
                 d["L"], weights["dtype"])


# ---------------------------------------------------------------------
# the parts
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
    return jnp.matmul(x, w)


def _norm(x, gain, eps):
    """The zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + gain)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _deltanet(h, w, d, quant, fault):
    B, T, _ = h.shape
    Hk, Hv, dk, dv, K = d["Hk"], d["Hv"], d["dk"], d["dv"], d["K"]
    r = Hv // Hk
    qkvz = _mm(h, w["w_qkvz"].T, quant).reshape(B, T, Hk,
                                                 2 * dk + 2 * r * dv)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(B, T, Hv, dv)
    ba = _mm(h, w["w_ba"], quant).reshape(B, T, Hk, 2 * r)
    b, a = ba[..., :r].reshape(B, T, Hv), ba[..., r:].reshape(B, T, Hv)
    mixed = jnp.concatenate([q.reshape(B, T, -1), k.reshape(B, T, -1),
                             v.reshape(B, T, -1)], -1)
    # causal depthwise convolution: column t reads t-K+1 .. t
    padded = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + T] * w["conv_w"][j]
                            for j in range(K)))
    kd = Hk * dk
    q = jnp.repeat(mixed[..., :kd].reshape(B, T, Hk, dk), r, axis=2)
    k = jnp.repeat(mixed[..., kd:2 * kd].reshape(B, T, Hk, dk), r, axis=2)
    v = mixed[..., 2 * kd:].reshape(B, T, Hv, dv)
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"])

    def step(S, col):
        q_t, k_t, v_t, g_t, b_t = col      # (B,Hv,dk) x2, (B,Hv,dv), (B,Hv)
        S = jnp.exp(g_t)[..., None, None] * S
        read = (0.0 if fault == "delta_dropped"
                else jnp.sum(S * k_t[..., :, None], -2))
        S = S + k_t[..., :, None] * (b_t[..., None] * (v_t - read))[
            ..., None, :]
        return S, jnp.sum(S * q_t[..., :, None], -2)

    _, o = jax.lax.scan(
        step, jnp.zeros((B, Hv, dk, dv), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                           # (B, T, Hv, dv)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + d["eps"])
    y = o * w["gate_norm"] * jax.nn.silu(z)
    return _mm(y.reshape(B, T, Hv * dv), w["out_proj"], quant)


def _rotate(x, pos, d):
    """Rotary embedding on the first ``rot`` dimensions of x (B, T, n,
    hd): ``x cos + rotate_half(x) sin`` there, the rest passed."""
    rot = d["rot"]
    inv = 1.0 / (d["theta"] ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = pos[:, None].astype(F32) * inv                 # (T, rot/2)
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    part, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-part[..., rot // 2:], part[..., :rot // 2]], -1)
    return jnp.concatenate([part * jnp.cos(emb) + half * jnp.sin(emb),
                            rest], -1)


def _attention(h, w, d, quant, fault):
    """Causal, one query head at a time, its scores never kept."""
    B, T, _ = h.shape
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    qg = _mm(h, w["wq"].T, quant).reshape(B, T, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(B, T, H * hd)
    k = _mm(h, w["wk"], quant).reshape(B, T, KVH, hd)
    v = _mm(h, w["wv"], quant).reshape(B, T, KVH, hd)
    pos = jnp.arange(T)
    q = _rotate(_norm(q, w["q_norm"], d["eps"]), pos, d)
    k = _rotate(_norm(k, w["k_norm"], d["eps"]), pos, d)
    keep = pos[:, None] >= pos[None, :]

    def one(args):
        qh, kh, vh = args                                   # (B, T, hd)
        if quant == "int8":
            qh, kh = _qdq(qh, -1), _qdq(kh, -1)
        s = jnp.einsum("bqd,bsd->bqs", qh * hd ** -0.5, kh)
        p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
        if quant == "int8":
            p, vh = _qdq(p, -1), _qdq(vh, 1)
        return jnp.einsum("bqs,bsd->bqd", p, vh)

    kv_of = jnp.arange(H) // (H // KVH)
    out = jax.lax.map(one, (jnp.moveaxis(q, 2, 0),
                            jnp.moveaxis(k, 2, 0)[kv_of],
                            jnp.moveaxis(v, 2, 0)[kv_of]))
    out = jnp.moveaxis(out, 0, 2).reshape(B, T, H * hd)
    if fault != "gate_dropped":
        out = out * jax.nn.sigmoid(gate)
    return _mm(out, w["wo"], quant)


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def _experts(h, w, d, quant, fault, live):
    B, T, D = h.shape
    N, k, held = B * T, d["top_k"], d["held"]
    hf = h.reshape(N, D)
    p = jax.nn.softmax(_mm(hf, w["router"], quant), -1)
    chosen, idx = jax.lax.top_k(p, k)
    wts = chosen / jnp.sum(chosen, -1, keepdims=True)
    first = d["first"] + (held if fault == "experts_shifted" else 0)
    local = idx - first
    mine = (local >= 0) & (local < held) & live.reshape(N)[:, None]
    if fault == "routed_dropped":
        mine = jnp.zeros_like(mine)
    # the held assignments sorted by expert, each expert's run of them
    # cut into tiles of TILE rows: tile t belongs to one expert
    expert = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(expert)
    count = jnp.zeros((held + 1,), jnp.int32).at[expert].add(1)[:held]
    start = jnp.cumsum(count) - count                   # in ``order``
    tiles = (count + TILE - 1) // TILE
    tiles_end = jnp.cumsum(tiles)
    rows_of = order // k
    wts_of = wts.reshape(-1)[order]

    def tile(t, out):
        e = jnp.searchsorted(tiles_end, t, side="right")
        at = start[e] + (t - (tiles_end[e] - tiles[e])) * TILE
        j = at + jnp.arange(TILE)
        ok = j < start[e] + count[e]
        j = jnp.where(ok, j, 0)
        rows = rows_of[j]
        y = _swiglu(hf[rows], w["moe_gate"][e], w["moe_up"][e],
                    w["moe_down"][e], quant)
        return out.at[rows].add(jnp.where(ok, wts_of[j], 0.0)[:, None] * y)

    routed = jax.lax.fori_loop(0, tiles_end[-1], tile,
                               jnp.zeros((N, D), F32))
    shared = _swiglu(hf, w["ws_gate"], w["ws_up"], w["ws_down"], quant)
    shared = shared * jax.nn.sigmoid(_mm(hf, w["shared_gate"], quant))
    return (routed + shared).reshape(B, T, D)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, kind, dk, quant, fault, live):
    d = dict(dk)
    w = {k: a.astype(F32) for k, a in w.items()}
    with jax.default_matmul_precision("highest"):
        h = _norm(x, w["norm"], d["eps"])
        if kind == "E":
            out = _experts(h, w, d, quant, fault, live)
        elif kind == "F":
            out = _attention(h, w, d, quant, fault)
        else:
            out = _deltanet(h, w, d, quant, fault)
    return x + out


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def final_hidden(weights: dict, tokens: np.ndarray, d: dict,
                 quant: str | None = None,
                 lengths: np.ndarray | None = None) -> jax.Array:
    """(B, T, D) float32: the final norm's output over right-padded rows
    ``tokens`` (B, T), the head not yet applied. ``quant`` is a control
    precision or one of ``FAULTS``; ``lengths`` (B,) the real columns
    of each row (the others reach no expert; every part is causal, so
    they reach no real column either). Part by part: a part's weights
    are made, used by one jitted call and freed."""
    dk = tuple(sorted(d.items()))
    fault, quant = (quant, None) if quant in FAULTS else (None, quant)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    live = (jnp.ones((B, T), bool) if lengths is None else
            jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None])
    x = _embed(top_weights(weights, d, "embed/tokens"), tokens)
    for at, kind in enumerate(d["pattern"]):
        x = _layer(x, layer_weights(weights, d, at), kind, dk, quant, fault,
                   live)
    return _final_norm(x, top_weights(weights, d, "norm_f"), d["eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _final_norm(x, gain, eps):
    return _norm(x, gain.astype(F32), eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_block(x, head, picked, quant):
    with jax.default_matmul_precision("highest"):
        logits = _mm(x, head, quant)                    # (rows, V)
    at = jnp.take_along_axis(logits, jnp.maximum(picked, 0)[:, None],
                             -1)[:, 0]
    best = logits.max(-1)
    return (jnp.where(picked >= 0, best - at, 0.0),
            jnp.argmax(logits, -1).astype(jnp.int32), jnp.abs(logits).max())


def head_readings(weights: dict, x: jax.Array, d: dict, picked,
                  quant: str | None = None):
    """The head over the columns of ``x`` (B, T, D) where ``picked``
    (B, T) is not -1, ``HEAD_ROWS`` rows at a time: per column the gap
    by which ``picked`` lies below the best logit, the argmax, and the
    largest logit's size over those columns. The other columns read a
    gap of 0 and an argmax of -1. ``quant`` as for the matmuls."""
    quant = None if quant in FAULTS else quant
    B, T, D = x.shape
    picked = np.asarray(picked, np.int32).reshape(B * T)
    at = np.flatnonzero(picked >= 0)
    n = len(at)
    # whole blocks of rows: the block's one program serves every seed
    at = np.concatenate([at, np.full(-n % HEAD_ROWS, at[0] if n else 0)])
    head = top_weights(weights, d, "lm_head").astype(F32)
    xs = x.reshape(B * T, D)
    gaps, tops, big = [], [], 0.0
    for lo in range(0, len(at), HEAD_ROWS):
        rows = jnp.asarray(at[lo:lo + HEAD_ROWS])
        gap, top, m = _head_block(xs[rows], head,
                                  jnp.asarray(picked)[rows], quant)
        gaps.append(gap)
        tops.append(top)
        big = jnp.maximum(big, m)
    gap = np.zeros(B * T, np.float32)
    top = np.full(B * T, -1, np.int32)
    if n:
        gap[at[:n]] = np.asarray(jnp.concatenate(gaps))[:n]
        top[at[:n]] = np.asarray(jnp.concatenate(tops))[:n]
    return gap.reshape(B, T), top.reshape(B, T), float(big)


def forward_logits(weights: dict, tokens: np.ndarray, d: dict,
                   quant: str | None = None) -> jax.Array:
    """(B, T, V) float32 logits, whole: for the tests' small sizes."""
    x = final_hidden(weights, tokens, d, quant)
    head = top_weights(weights, d, "lm_head").astype(F32)
    with jax.default_matmul_precision("highest"):
        return _mm(x, head, None if quant in FAULTS else quant)
