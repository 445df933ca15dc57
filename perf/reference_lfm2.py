"""The plain reference of the ``lfm2_moe`` family: its forward pass in
straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching of requests. It imports nothing of the program and takes
nothing the program made: its weights come from the seed by the
initialisation rule written out below, its inputs from the
benchmark's own traffic generator.

Architecture (``LiquidAI/LFM2-8B-A1B`` ``config.json``, ``model_type``
``lfm2_moe``), as transformers' ``modeling_lfm2_moe.py`` reads that
config (each point below marked "reading" is a key of the
configuration file's ``assumed``): every layer is ``h <- x +
mixer(N1(x))``, ``out <- h + ffn(N2(h))``, every ``N`` the plain
RMSNorm ``x / rms(x) w`` (eps ``norm_eps``; ``w``, not ``1 + w``,
reading); a final ``N``; the head is the embedding's transpose (tied,
reading).

- ``conv`` mixer, gated short convolution: ``[B | C | x] = h W_in``
  (this order, reading), ``Bx = B x``, ``z_t = sum_{j=0..2} w_j Bx_{t-2+j}``
  with zeros before the sequence (``conv_L_cache`` 3, no bias), ``y =
  C z``, ``W_out y``.
- ``full_attention`` mixer, 32 heads and 8 key/value heads of 2048 /
  32 = 64: q, k, v; ``N`` over q and k a head; RoPE (theta
  ``rope_theta``, ``rotate_half`` over the whole head, reading); the
  causal mask; softmax at scale ``64^-1/2``, query head ``h`` reading
  key/value head ``h // 4``; ``W_o``.
- ffn: the first ``num_dense_layers`` a SwiGLU ``w2 (silu(w1 x) w3 x)``
  of ``intermediate_size``; after them ``s = sigmoid(x W_r)`` over
  ``num_experts`` in float32, the ``num_experts_per_tok`` largest of
  ``s + expert_bias`` chosen (``use_expert_bias``), their ``s`` divided
  by ``(sum + 1e-6)`` (``norm_topk_prob``; the 1e-6 reading) and times
  ``routed_scaling_factor``; SwiGLU experts of
  ``moe_intermediate_size``; no shared expert.

Departures from the published description, each also a key of the
configuration file's ``assumed``:

- the layers are the first ``num_hidden_layers`` of the published
  ``layer_types`` (a pipeline stage), every expert held;
- weights are random from the seed (the rule is ``_draw``'s), the
  router's bias drawn as a matrix is.

Forced by memory and time, none changing a value: a layer's weights
are made from the seed when the layer runs and freed after it;
attention runs one query head and ``QBLOCK`` queries at a time; the
routed experts run over their own rows only (the assignments sorted
by expert, a fixed tile of rows at a time); padding columns
(``lengths``) reach no expert; the head is applied to the columns
compared alone.

``quant="int8"`` is the control: every matmul operand is rounded to
eight bits (symmetric, scaled along the contracted axis) before it is
multiplied, the precision just below the bfloat16 the configuration
states. ``quant="bf16"`` is a second witness, not a control: every
matmul operand and the residual stream after each part rounded to
bfloat16, the products summed in float32, as the program computes.
Planted faults, each at full precision (``FAULTS``), to show what the
comparison sees of each mechanism: ``"b_gate_dropped"`` convolves
``x`` for ``B x``, ``"c_gate_dropped"`` outputs ``z`` for ``C z``,
``"tail_dropped"`` lets the convolution read the column itself alone
(what a tail of zeros carried into every step leaves),
``"bias_ignored"`` chooses the experts by ``s`` alone.

``routes`` makes a pass run the experts another pass chose (the
program's, from its cache's log, or a control's), weighted by its own
scores, and say where its own choice would have been another
(``routed_hidden``): near a tie among 32 sigmoid scores a rounding
flips the choice, and with no shared expert a flip moves the residual
by a quarter of the layer's output, so a comparison that let each side
choose for itself would read the flips and not the arithmetic.
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
#: what the family divides the chosen scores by, beside their sum
ROUTE_EPS = 1e-6

#: leaf names a kind, in the order the program's tree flattens them
KIND_LEAVES = {
    "A": ("k_norm", "norm", "q_norm", "wk", "wo", "wq", "wv"),
    "C": ("conv_w", "in_proj", "norm", "out_proj"),
    "D": ("norm", "w_down", "w_gate", "w_up"),
    "E": ("expert_bias", "moe_down", "moe_gate", "moe_up", "norm",
          "router"),
}
#: the program's stacks, in the sorted order of its tree
STACKS = (("blocks_a", "A"), ("blocks_c", "C"), ("blocks_d", "D"),
          ("blocks_e", "E"))
TOP_LEAVES = ("embed/tokens", "norm_f")
KEPT_F32 = ("router", "expert_bias")
RESIDUAL = ("out_proj", "wo", "w_down", "moe_down")
FAULTS = ("b_gate_dropped", "c_gate_dropped", "tail_dropped",
          "bias_ignored")


def dims_of(config: dict) -> dict:
    """The sizes the equations need, from the configuration file."""
    types = config["layer_types"]
    dense = config["num_dense_layers"]
    D, H = config["hidden_size"], config["num_attention_heads"]
    return {
        "pattern": "".join(
            ("C" if t == "conv" else "A") + ("D" if i < dense else "E")
            for i, t in enumerate(types)),
        "L": config["num_hidden_layers"], "D": D, "V": config["vocab_size"],
        "H": H, "KVH": config["num_key_value_heads"], "hd": D // H,
        "K": config["conv_L_cache"], "theta": float(config["rope_theta"]),
        "Fd": config["intermediate_size"], "E": config["num_experts"],
        "top_k": config["num_experts_per_tok"],
        "F": config["moe_intermediate_size"],
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["norm_eps"]),
    }


def leaf_names(d: dict) -> list[str]:
    """Every leaf the program's tree has, in its flattened order."""
    names = [f"{stack}/{leaf}" for stack, kind in STACKS
             if kind in d["pattern"] for leaf in KIND_LEAVES[kind]]
    # the tree flattens with its dict keys sorted at every level
    return sorted(names + list(TOP_LEAVES), key=lambda n: n.split("/"))


def layer_shapes(d: dict, kind: str) -> dict:
    """One layer's leaves of ``kind``, matrices (out, in) as the
    program keeps them (the experts (expert, in, out))."""
    D = d["D"]
    if kind == "C":
        return {"norm": (D,), "in_proj": (3 * D, D), "conv_w": (d["K"], D),
                "out_proj": (D, D)}
    if kind == "A":
        H, KVH, hd = d["H"], d["KVH"], d["hd"]
        return {"norm": (D,), "wq": (H * hd, D), "wk": (KVH * hd, D),
                "wv": (KVH * hd, D), "q_norm": (hd,), "k_norm": (hd,),
                "wo": (D, H * hd)}
    if kind == "D":
        return {"norm": (D,), "w_gate": (d["Fd"], D), "w_up": (d["Fd"], D),
                "w_down": (D, d["Fd"])}
    return {"norm": (D,), "router": (D, d["E"]), "expert_bias": (d["E"],),
            "moe_gate": (d["E"], D, d["F"]), "moe_up": (d["E"], D, d["F"]),
            "moe_down": (d["E"], d["F"], D)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, name, shape, n_layers, kernel, dtype):
    """The initialisation rule, one leaf of one layer: gains (every name
    with ``norm`` in it) 1; the convolution's taps normal with
    deviation ``kernel ** -0.5``; every other leaf normal with
    deviation 0.02, the four that write into the residual stream
    (``out_proj``, ``wo``, ``w_down``, ``moe_down``) 0.02 / sqrt(2 L);
    stored in ``dtype``, the router's two leaves then widened to
    float32."""
    if "norm" in name:
        return jnp.ones(shape, dtype)
    scale = (kernel ** -0.5 if name == "conv_w"
             else 0.02 / (2.0 * n_layers) ** 0.5 if name in RESIDUAL
             else 0.02)
    leaf = (jax.random.normal(key, shape) * scale).astype(dtype)
    return leaf.astype(F32) if name in KEPT_F32 else leaf


def init_weights(d: dict, seed: int, dtype) -> dict:
    """A handle, not the weights: the seed and the dtype they are
    stored in. ``layer_weights`` and ``top_weights`` make a layer's
    leaves when it runs. One key a leaf, split from ``key(seed)`` in
    the order of ``leaf_names``; within a leaf one key a layer of its
    kind, split again (the program draws a stack's layers each from its
    own key, and the matrices a layer each likewise)."""
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
        out[name] = _draw(k, name, shape, d["L"], d["K"], weights["dtype"])
    return out


def top_weights(weights: dict, d: dict, name: str):
    shape = {"embed/tokens": (d["V"], d["D"]), "norm_f": (d["D"],)}[name]
    return _draw(_leaf_key(weights, d, name), name.split("/")[-1], shape,
                 d["L"], d["K"], weights["dtype"])


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


def _short_conv(h, w, d, quant, fault):
    """The gated short convolution over whole rows, zeros before each."""
    B, T, D = h.shape
    K = d["K"]
    b, c, x = jnp.split(_mm(h, w["in_proj"].T, quant), 3, -1)
    bx = x if fault == "b_gate_dropped" else b * x
    if fault == "tail_dropped":
        z = w["conv_w"][K - 1] * bx
    else:
        seq = jnp.concatenate([jnp.zeros((B, K - 1, D), F32), bx], 1)
        z = sum(w["conv_w"][j] * seq[:, j:j + T] for j in range(K))
    y = z if fault == "c_gate_dropped" else c * z
    return _mm(y, w["out_proj"].T, quant)


def _attention(h, w, d, quant):
    """One query head and ``QBLOCK`` queries at a time, over the keys
    the causal mask lets them reach."""
    B, T, _ = h.shape
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    q = _mm(h, w["wq"].T, quant).reshape(B, T, H, hd)
    k = _mm(h, w["wk"].T, quant).reshape(B, T, KVH, hd)
    v = _mm(h, w["wv"].T, quant).reshape(B, T, KVH, hd)
    q, k = _norm(q, w["q_norm"], d["eps"]), _norm(k, w["k_norm"], d["eps"])
    pos = jnp.arange(T)
    q, k = _rotate(q, pos, d), _rotate(k, pos, d)
    qb = T if T <= QBLOCK else QBLOCK
    if T % qb:
        raise ValueError(f"{T} columns are no whole number of blocks")

    def block(qh, kh, vh, b0):             # (B, qb, hd), (B, T, hd) x2
        keep = (jnp.arange(T)[None, :]
                <= b0 + jnp.arange(qb)[:, None])
        if quant == "int8":
            qh, kh = _qdq(qh, -1), _qdq(kh, -1)
        elif quant == "bf16":
            qh, kh = _bf16(qh), _bf16(kh)
        s = jnp.einsum("bqd,bsd->bqs", qh * hd ** -0.5, kh)
        p = jax.nn.softmax(jnp.where(keep, s, NEG), -1)
        if quant == "int8":
            p, vh = _qdq(p, -1), _qdq(vh, 1)
        elif quant == "bf16":
            p, vh = _bf16(p), _bf16(vh)
        return jnp.einsum("bqs,bsd->bqd", p, vh)

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
    return _mm(jnp.moveaxis(out, 0, 2).reshape(B, T, H * hd), w["wo"].T,
               quant)


def _swiglu(x, gate, up, down, quant):
    """``down(silu(x gate) * x up)``, each matrix (in, out)."""
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def _experts(h, w, d, quant, fault, live, forced=None):
    """The routed sum over ``h`` (B, T, D), and (the experts run (B, T,
    k), whether this pass's own choice is another set (B, T), and by
    how much its own choice's ``s + bias`` sum exceeds that of the
    experts run (B, T), 0 where they agree). ``forced`` (B, T, k) are
    the experts to run, or None for the pass's own choice."""
    B, T, D = h.shape
    N, k, E = B * T, d["top_k"], d["E"]
    hf = h.reshape(N, D)
    s = jax.nn.sigmoid(_mm(hf, w["router"], quant))
    z = s if fault == "bias_ignored" else s + w["expert_bias"]
    best, own = jax.lax.top_k(z, k)
    idx = own if forced is None else forced.reshape(N, k).astype(jnp.int32)
    flip = jnp.any(jnp.sort(idx, -1) != jnp.sort(own, -1), -1)
    gap = jnp.where(flip, jnp.sum(best, -1)
                    - jnp.sum(jnp.take_along_axis(z, idx, -1), -1), 0.0)
    chosen = jnp.take_along_axis(s, idx, -1)
    wts = chosen / (jnp.sum(chosen, -1, keepdims=True) + ROUTE_EPS) * d[
        "scale"]
    mine = jnp.broadcast_to(live.reshape(N)[:, None], idx.shape)
    # the assignments sorted by expert, each expert's run of them cut
    # into tiles of TILE rows: tile t belongs to one expert
    expert = jnp.where(mine, idx, E).reshape(-1)
    order = jnp.argsort(expert)
    count = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
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
    return routed.reshape(B, T, D), (idx.reshape(B, T, k),
                                     flip.reshape(B, T), gap.reshape(B, T))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, w, kind, dk, quant, fault, live, forced=None):
    """One part, ``x + part(norm(x))``, and an expert part's record of
    its choice (``_experts``), None for the others."""
    d = dict(dk)
    w = {k: a.astype(F32) for k, a in w.items()}
    record = None
    with jax.default_matmul_precision("highest"):
        h = _norm(x, w["norm"], d["eps"])
        if kind == "E":
            out, record = _experts(h, w, d, quant, fault, live, forced)
        elif kind == "D":
            out = _swiglu(h, w["w_gate"].T, w["w_up"].T, w["w_down"].T,
                          quant)
        elif kind == "C":
            out = _short_conv(h, w, d, quant, fault)
        else:
            out = _attention(h, w, d, quant)
    if quant == "bf16":
        return _bf16(x + _bf16(out)), record
    return x + out, record


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


def final_hidden(weights: dict, tokens: np.ndarray, d: dict,
                 quant: str | None = None,
                 lengths: np.ndarray | None = None):
    """(B, T, D) float32: the final norm's output over right-padded rows
    ``tokens`` (B, T), the head not yet applied. ``quant`` is a control
    precision, the witness ``"bf16"`` or one of ``FAULTS``; ``lengths``
    (B,) the real columns of each row (the others reach no expert; every
    part is causal, so they reach no real column either). Part by part:
    a part's weights are made, used by one jitted call and freed."""
    return routed_hidden(weights, tokens, d, quant, lengths)[0]


def routed_hidden(weights: dict, tokens: np.ndarray, d: dict,
                  quant: str | None = None,
                  lengths: np.ndarray | None = None,
                  routes: np.ndarray | None = None):
    """``final_hidden``'s output, and the expert parts' choices on the
    host: ``{"routes"}`` the experts run, (B, T, Le, k) int32, and
    ``{"flip"}`` and ``{"gap"}`` (B, T, Le) where the pass's own choice
    was another set and by how much its ``s + bias`` sum exceeded that
    of the experts run. ``routes`` (B, T, Le, k) makes the pass run
    those experts (another pass's choice) and weigh them by its own
    scores."""
    dk = tuple(sorted((k, v) for k, v in d.items()))
    fault, quant = (quant, None) if quant in FAULTS else (None, quant)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    live = (jnp.ones((B, T), bool) if lengths is None else
            jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None])
    x = _embed(top_weights(weights, d, "embed/tokens"), tokens)
    if quant == "bf16":
        x = _bf16(x)
    records = []
    for at, kind in enumerate(d["pattern"]):
        forced = (None if routes is None or kind != "E" else
                  jnp.asarray(routes[:, :, len(records)], jnp.int32))
        x, record = _layer(x, layer_weights(weights, d, at), kind, dk,
                           quant, fault, live, forced)
        if record is not None:
            records.append([np.asarray(a) for a in record])
    chosen = {name: np.stack([r[j] for r in records], axis=2)
              for j, name in enumerate(("routes", "flip", "gap"))}
    return (_final_norm(x, top_weights(weights, d, "norm_f"), d["eps"]),
            chosen)


@functools.partial(jax.jit, static_argnums=(2,))
def _final_norm(x, gain, eps):
    return _norm(x, gain.astype(F32), eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _head_block(x, table, quant):
    """The tied head: the embedding's rows, transposed."""
    with jax.default_matmul_precision("highest"):
        return _mm(x, table.astype(F32).T, quant)


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
    table = top_weights(weights, d, "embed/tokens")
    xs = x.reshape(B * T, D)
    out = [np.asarray(_head_block(xs[jnp.asarray(flat[lo:lo + HEAD_ROWS])],
                                  table, quant))
           for lo in range(0, len(flat), HEAD_ROWS)]
    return np.concatenate(out)[:n] if out else np.zeros((0, d["V"]))


def forward_logits(weights: dict, tokens: np.ndarray, d: dict,
                   quant: str | None = None) -> jax.Array:
    """(B, T, V) float32 logits, whole: for the tests' small sizes."""
    x = final_hidden(weights, tokens, d, quant)
    table = top_weights(weights, d, "embed/tokens").astype(F32)
    with jax.default_matmul_precision("highest"):
        return _mm(x, table.T, None if quant in FAULTS else quant)
