"""The plain reference of the ``afmoe`` family: its forward pass in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching of requests. It imports nothing of the program and takes
nothing the program made: its weights come from the seed by the
initialisation rule written out below, its inputs from the
benchmark's own traffic generator.

Architecture (``arcee-ai/Trinity-Mini`` ``config.json``, ``model_type``
``afmoe``), as transformers' ``modeling_afmoe.py`` reads that config
(each point below marked "reading" is the configuration file's
``assumed``): every layer is ``x <- x + N2(attn(N1(x)))``, ``x <- x +
N4(mlp(N3(x)))`` (sandwich norms, reading), every ``N`` the plain
RMSNorm ``x / rms(x) w`` (eps ``rms_norm_eps``); the embedding times
``sqrt(hidden_size)`` (``mup_enabled``, reading); a final ``N``; an
untied head.

- attention, 32 heads and 4 key/value heads of 128: q, k, v; ``N``
  over q and k a head (reading); on a ``sliding_attention`` layer RoPE
  (theta ``rope_theta``, ``rotate_half`` over the whole head) and a
  window, query ``p`` reading keys ``p - sliding_window + 1 .. p``;
  a ``full_attention`` layer no positional encoding at all (NoPE,
  reading) and the causal mask; softmax at scale ``head_dim^-1/2``,
  query head ``h`` reading key/value head ``h // (H / KVH)``; ``W_o
  (o sigmoid(x W_gate))``, ``x`` the normed input (reading).
- mlp: the first ``num_dense_layers`` a SwiGLU ``W_down (silu(W_gate
  x) W_up x)`` of ``intermediate_size``; after them ``s =
  sigmoid(x W_r)`` over ``router_width`` experts in float32, the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` chosen, their
  ``s`` renormalised to sum to 1 (``route_norm``) and times
  ``route_scale``; SwiGLU experts of ``moe_intermediate_size``; one
  shared SwiGLU expert of the same width, ungated. ``n_group`` =
  ``topk_group`` = 1: the group-limited choice is the plain one.

Departures from the published description, each also a key of the
configuration file's ``assumed`` or ``omitted``:

- **a share of the experts**: the chip holds experts ``first .. first
  + held`` of the router's ``router_width``; the routed sum runs over
  the chosen experts that are held (the program does the same; the
  shares add up to the whole layer because the sum is linear);
- the layers are the first ``num_hidden_layers`` of the published
  ``layer_types`` (a pipeline stage);
- weights are random from the seed (the rule is ``_draw``'s), the
  router's bias drawn as a matrix is.

Forced by memory and time, none changing a value: a layer's weights
are made from the seed when the layer runs and freed after it;
attention runs one query head and ``QBLOCK`` queries at a time, a
window layer's over the keys its window can reach; the routed experts
run over their own rows only (the assignments sorted by expert, a
fixed tile of rows at a time); padding columns (``lengths``) reach no
expert; the head is applied to the columns compared alone.

``quant="int8"`` is the control: every matmul operand is rounded to
eight bits (symmetric, scaled along the contracted axis) before it is
multiplied, the precision just below the bfloat16 the configuration
states. ``quant="bf16"`` is a second witness, not a control: every
matmul operand and the residual stream after each part rounded to
bfloat16, the products summed in float32, as the program computes; it
reads what the configuration's own precision moves of the logits, with
this code's order of operations, not the program's. Planted faults, each at full precision (``FAULTS``), to show
what the comparison sees of each mechanism: ``"window_dropped"``
attends every earlier key on the window layers, ``"routed_dropped"``
leaves the routed sum out of every expert layer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -2.0 ** 30
F32 = jnp.float32
#: rows of a tile of one expert's assignments
TILE = 128
#: queries a step of the attention
QBLOCK = 512
#: rows the head is applied to at a time
HEAD_ROWS = 256

#: leaf names a kind, in the order the program's tree flattens them
KIND_LEAVES = {
    "D": ("norm", "post_norm", "w_down", "w_gate", "w_up"),
    "E": ("expert_bias", "moe_down", "moe_gate", "moe_up", "norm",
          "post_norm", "router", "ws_down", "ws_gate", "ws_up"),
    "F": ("k_norm", "norm", "post_norm", "q_norm", "w_gate", "wk", "wo",
          "wq", "wv"),
}
KIND_LEAVES["W"] = KIND_LEAVES["F"]
#: the program's stacks, in the sorted order of its tree
STACKS = (("blocks_d", "D"), ("blocks_e", "E"), ("blocks_f", "F"),
          ("blocks_w", "W"))
TOP_LEAVES = ("embed/tokens", "lm_head", "norm_f")
KEPT_F32 = ("router", "expert_bias")
RESIDUAL = ("wo", "w_down", "moe_down", "ws_down")
FAULTS = ("window_dropped", "routed_dropped")


def dims_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file."""
    types = config["layer_types"]
    dense = config["num_dense_layers"]
    return {
        "pattern": "".join(
            ("W" if t == "sliding_attention" else "F")
            + ("D" if i < dense else "E") for i, t in enumerate(types)),
        "L": config["num_hidden_layers"], "D": config["hidden_size"],
        "V": config["vocab_size"], "H": config["num_attention_heads"],
        "KVH": config["num_key_value_heads"], "hd": config["head_dim"],
        "window": config["sliding_window"],
        "theta": float(config["rope_theta"]),
        "Fd": config["intermediate_size"],
        "held": config["num_experts"], "router": config["router_width"],
        "first": config["experts_held_first"],
        "top_k": config["num_experts_per_tok"],
        "F": config["moe_intermediate_size"],
        "Fs": config["moe_intermediate_size"] * config["num_shared_experts"],
        "scale": float(config["route_scale"]),
        "eps": float(config["rms_norm_eps"]),
    }


def leaf_names(d: dict) -> list[str]:
    """Every leaf the program's tree has, in its flattened order."""
    names = [f"{stack}/{leaf}" for stack, kind in STACKS
             if kind in d["pattern"] for leaf in KIND_LEAVES[kind]]
    # the tree flattens with its dict keys sorted at every level
    return sorted(names + list(TOP_LEAVES), key=lambda n: n.split("/"))


def layer_shapes(d: dict, kind: str) -> dict:
    """One layer's leaves of ``kind``."""
    D = d["D"]
    if kind in "WF":
        H, KVH, hd = d["H"], d["KVH"], d["hd"]
        return {"norm": (D,), "post_norm": (D,), "wq": (H * hd, D),
                "wk": (KVH * hd, D), "wv": (KVH * hd, D), "q_norm": (hd,),
                "k_norm": (hd,), "w_gate": (D, H * hd), "wo": (H * hd, D)}
    if kind == "D":
        return {"norm": (D,), "post_norm": (D,), "w_gate": (D, d["Fd"]),
                "w_up": (D, d["Fd"]), "w_down": (d["Fd"], D)}
    return {"norm": (D,), "post_norm": (D,), "router": (D, d["router"]),
            "expert_bias": (d["router"],),
            "moe_gate": (d["held"], D, d["F"]),
            "moe_up": (d["held"], D, d["F"]),
            "moe_down": (d["held"], d["F"], D),
            "ws_gate": (D, d["Fs"]), "ws_up": (D, d["Fs"]),
            "ws_down": (d["Fs"], D)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, name, shape, n_layers, dtype):
    """The initialisation rule, one leaf of one layer: gains
    (every name with ``norm`` in it) 1; every other leaf normal with
    deviation 0.02, the four that write into the residual stream
    (``wo``, ``w_down``, ``moe_down``, ``ws_down``) 0.02 / sqrt(2 L);
    stored in ``dtype``, the router's two leaves then widened to
    float32."""
    if "norm" in name:
        return jnp.ones(shape, dtype)
    scale = (0.02 / (2.0 * n_layers) ** 0.5 if name in RESIDUAL
             else 0.02)
    leaf = (jax.random.normal(key, shape) * scale).astype(dtype)
    return leaf.astype(F32) if name in KEPT_F32 else leaf


def init_weights(d: dict, seed: int, dtype) -> dict:
    """A handle, not the weights: the seed and the dtype they are
    stored in. ``layer_weights`` and ``top_weights`` make a layer's
    leaves when it runs. One key a leaf, split from ``key(seed)`` in
    the order of ``leaf_names``; within a leaf one key a layer of its
    kind, split again (the program draws a stack's layers each from its
    own key, and the expert matrices a layer each likewise)."""
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


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _mm(x, w, quant):
    """x (..., K) @ w (K, N)."""
    if quant == "int8":
        x, w = _qdq(x, -1), _qdq(w, 0)
    elif quant == "bf16":
        x, w = _bf16(x), _bf16(w)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rotate(x, pos, d):
    """RoPE over the whole head: ``x cos + rotate_half(x) sin``,
    inverse frequencies ``theta^(-2i/hd)``; x (B, T, n, hd)."""
    hd = d["hd"]
    inv = 1.0 / (d["theta"] ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * inv                 # (T, hd/2)
    emb = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


def _attention(h, w, d, quant, fault, windowed):
    """One query head and ``QBLOCK`` queries at a time, over the keys
    their mask can reach."""
    B, T, _ = h.shape
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    q = _mm(h, w["wq"].T, quant).reshape(B, T, H, hd)
    k = _mm(h, w["wk"].T, quant).reshape(B, T, KVH, hd)
    v = _mm(h, w["wv"].T, quant).reshape(B, T, KVH, hd)
    q, k = _norm(q, w["q_norm"], d["eps"]), _norm(k, w["k_norm"], d["eps"])
    pos = jnp.arange(T)
    if windowed:
        q, k = _rotate(q, pos, d), _rotate(k, pos, d)
    window = d["window"] if windowed and fault != "window_dropped" else None
    qb = T if T <= QBLOCK else QBLOCK
    if T % qb:
        raise ValueError(f"{T} columns are no whole number of blocks")
    reach = T if window is None else min(T, qb + window)

    def block(qh, kh, vh, b0):             # (B, qb, hd), (B, T, hd) x2
        start = jnp.clip(b0 + qb - reach, 0, T - reach)
        ks = jax.lax.dynamic_slice_in_dim(kh, start, reach, 1)
        vs = jax.lax.dynamic_slice_in_dim(vh, start, reach, 1)
        qp = b0 + jnp.arange(qb)[:, None]
        kp = start + jnp.arange(reach)[None, :]
        keep = kp <= qp
        if window is not None:
            keep &= qp - kp < window
        if quant == "int8":
            qh, ks = _qdq(qh, -1), _qdq(ks, -1)
        elif quant == "bf16":
            qh, ks = _bf16(qh), _bf16(ks)
        s = jnp.einsum("bqd,bsd->bqs", qh * hd ** -0.5, ks)
        p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
        if quant == "int8":
            p, vs = _qdq(p, -1), _qdq(vs, 1)
        elif quant == "bf16":
            p, vs = _bf16(p), _bf16(vs)
        return jnp.einsum("bqs,bsd->bqd", p, vs)

    def head(args):
        qh, kh, vh = args
        n = T // qb
        out = jax.lax.map(
            lambda a: block(a[0], kh, vh, a[1]),
            (jnp.moveaxis(qh.reshape(B, n, qb, hd), 1, 0),
             jnp.arange(n) * qb))
        return jnp.moveaxis(out, 0, 1).reshape(B, T, hd)

    kv_of = jnp.arange(H) // (H // KVH)
    out = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                             jnp.moveaxis(k, 2, 0)[kv_of],
                             jnp.moveaxis(v, 2, 0)[kv_of]))
    out = jnp.moveaxis(out, 0, 2).reshape(B, T, H * hd)
    return _mm(out * jax.nn.sigmoid(_mm(h, w["w_gate"], quant)), w["wo"],
               quant)


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def _experts(h, w, d, quant, fault, live):
    B, T, D = h.shape
    N, k, held = B * T, d["top_k"], d["held"]
    hf = h.reshape(N, D)
    s = jax.nn.sigmoid(_mm(hf, w["router"], quant))
    _, idx = jax.lax.top_k(s + w["expert_bias"], k)
    chosen = jnp.take_along_axis(s, idx, -1)
    wts = chosen / jnp.sum(chosen, -1, keepdims=True) * d["scale"]
    local = idx - d["first"]
    mine = (local >= 0) & (local < held) & live.reshape(N)[:, None]
    if fault == "routed_dropped":
        mine = jnp.zeros_like(mine)
    # the held assignments sorted by expert, each expert's run of them
    # cut into tiles of TILE rows: tile t belongs to one expert
    expert = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(expert)
    count = jnp.zeros((held + 1,), jnp.int32).at[expert].add(1)[:held]
    start = jnp.cumsum(count) - count
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
    return (routed + shared).reshape(B, T, D), _route_of(
        s + w["expert_bias"], idx, d)


def _route_of(z, idx, d):
    """What a column's choice was here and how near it came to another:
    the held experts chosen as a bit each (``held`` <= 31), and the
    smallest lead a choice held over a key it beat where one of the two
    is held (a chosen held expert over the best unchosen, or the least
    chosen over the best unchosen held expert): under it a rounding
    changes which held experts the column meets. (N, R) biased scores
    ``z`` and the chosen ``idx`` (N, k); ``(B, T)`` int32 and float32."""
    N, R = z.shape
    held = (jnp.arange(R) >= d["first"]) & (jnp.arange(R) < d["first"]
                                            + d["held"])
    chosen = jnp.zeros((N, R), bool).at[jnp.arange(N)[:, None], idx].set(
        True)
    zc = jnp.take_along_axis(z, idx, -1)
    inf = jnp.float32(jnp.inf)
    best_out = jnp.max(jnp.where(chosen, -inf, z), -1)
    best_out_held = jnp.max(jnp.where(chosen | ~held, -inf, z), -1)
    least_in_held = jnp.min(jnp.where(held[idx], zc, inf), -1)
    margin = jnp.minimum(least_in_held - best_out,
                         jnp.min(zc, -1) - best_out_held)
    bits = jnp.sum(jnp.where(chosen & held, 1 << jnp.clip(
        jnp.arange(R) - d["first"], 0, 30), 0), -1, dtype=jnp.int32)
    return bits, margin


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, kind, dk, quant, fault, live):
    """The part's output and, an expert part, its ``_route_of``."""
    d = dict(dk)
    w = {k: a.astype(F32) for k, a in w.items()}
    route = None
    with jax.default_matmul_precision("highest"):
        h = _norm(x, w["norm"], d["eps"])
        if kind == "E":
            out, route = _experts(h, w, d, quant, fault, live)
            route = tuple(r.reshape(x.shape[:2]) for r in route)
        elif kind == "D":
            out = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)
        else:
            out = _attention(h, w, d, quant, fault, kind == "W")
        out = _norm(out, w["post_norm"], d["eps"])
    if quant == "bf16":
        return _bf16(x + _bf16(out)), route
    return x + out, route


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, dim):
    return table[tokens].astype(F32) * dim ** 0.5


def final_hidden(weights: dict, tokens: np.ndarray, d: dict,
                 quant: str | None = None,
                 lengths: np.ndarray | None = None, routes: bool = False):
    """(B, T, D) float32: the final norm's output over right-padded rows
    ``tokens`` (B, T), the head not yet applied. ``quant`` is a control
    precision, the witness ``"bf16"`` or one of ``FAULTS``; ``lengths``
    (B,) the real columns of each row (the others reach no expert; every
    part is causal, so they reach no real column either). Part by part:
    a part's weights are made, used by one jitted call and freed. With
    ``routes`` also each expert part's ``_route_of``, a list of (bits,
    margin) (B, T) arrays in the order of the parts."""
    dk = tuple(sorted((k, v) for k, v in d.items()))
    fault, quant = (quant, None) if quant in FAULTS else (None, quant)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    live = (jnp.ones((B, T), bool) if lengths is None else
            jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None])
    x = _embed(top_weights(weights, d, "embed/tokens"), tokens, d["D"])
    if quant == "bf16":
        x = _bf16(x)
    chosen = []
    for at, kind in enumerate(d["pattern"]):
        x, route = _layer(x, layer_weights(weights, d, at), kind, dk, quant,
                          fault, live)
        if route is not None:
            chosen.append(route)
    x = _final_norm(x, top_weights(weights, d, "norm_f"), d["eps"])
    return (x, chosen) if routes else x


@functools.partial(jax.jit, static_argnums=(2,))
def _final_norm(x, gain, eps):
    return _norm(x, gain.astype(F32), eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _head_block(x, head, quant):
    with jax.default_matmul_precision("highest"):
        return _mm(x, head, quant)


def head_logits(weights: dict, x: jax.Array, d: dict, at,
                quant: str | None = None) -> np.ndarray:
    """(n, V) float32 logits of the columns ``at`` ((b, t) pairs) of
    ``x`` (B, T, D), ``HEAD_ROWS`` rows at a time (whole blocks: one
    program serves every seed)."""
    quant = None if quant in FAULTS else quant
    B, T, D = x.shape
    flat = np.asarray([b * T + t for b, t in at], np.int64)
    n = len(flat)
    flat = np.concatenate([flat, np.zeros(-n % HEAD_ROWS, np.int64)])
    head = top_weights(weights, d, "lm_head").astype(F32)
    xs = x.reshape(B * T, D)
    out = [np.asarray(_head_block(xs[jnp.asarray(flat[lo:lo + HEAD_ROWS])],
                                  head, quant))
           for lo in range(0, len(flat), HEAD_ROWS)]
    return np.concatenate(out)[:n] if out else np.zeros((0, d["V"]))


def forward_logits(weights: dict, tokens: np.ndarray, d: dict,
                   quant: str | None = None) -> jax.Array:
    """(B, T, V) float32 logits, whole: for the tests' small sizes."""
    x = final_hidden(weights, tokens, d, quant)
    head = top_weights(weights, d, "lm_head").astype(F32)
    with jax.default_matmul_precision("highest"):
        return _mm(x, head, None if quant in FAULTS else quant)
