"""The plain reference: the published decoder in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernels,
no cache, no batching of requests. It imports nothing of the program
and takes nothing the program made: its weights come from the seed by
the initialisation rule written out below, its inputs from the
benchmark's own traffic generator.

Architecture (``mistralai/Mistral-7B-v0.1``, ``modeling_mistral``):
pre-norm decoder blocks; RMSNorm; rotary embedding over split halves
with ``theta ** (-i / (head_dim / 2))``; grouped-query attention, query
head ``h`` reading key/value head ``h // (H / KVH)``, scores scaled by
``head_dim ** -0.5``, causal; SwiGLU feed-forward; untied head. The
sliding window (4096) is not applied: no sequence here is longer.

Departures, all forced by memory, none changing a value: layers run
one jitted call at a time in a Python loop, attention runs one
key/value head at a time under ``lax.map``, and the training step
keeps Adam's two moments on the host between steps.

``quant="int8"`` is the control: every matmul operand is rounded to
eight bits (symmetric, scaled along the contracted axis) before it is
multiplied — the precision just below the bfloat16 the configurations
state. Gradients pass straight through the rounding.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

IGNORE_INDEX = -100
NEG = -2.0 ** 30
F32 = jnp.float32

#: leaf -> shape rule, in the order the keys are split (sorted paths)
LEAVES = ("blocks/attn_norm", "blocks/mlp_norm", "blocks/w_down",
          "blocks/w_gate", "blocks/w_up", "blocks/wk", "blocks/wo",
          "blocks/wq", "blocks/wv", "embed/tokens", "lm_head", "out_norm")
NO_DECAY = ("blocks/attn_norm", "blocks/mlp_norm", "out_norm",
            "embed/tokens")


def dims_of(config: dict) -> dict:
    """The sizes the equations need, from a Hugging Face ``config.json``."""
    H = config["num_attention_heads"]
    return {"L": config["num_hidden_layers"], "D": config["hidden_size"],
            "H": H, "KVH": config["num_key_value_heads"],
            "hd": config.get("head_dim", config["hidden_size"] // H),
            "F": config["intermediate_size"], "V": config["vocab_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}


def leaf_shapes(d: dict) -> dict:
    L, D, H, KVH, hd, F, V = (d[k] for k in ("L", "D", "H", "KVH", "hd",
                                              "F", "V"))
    return {"blocks/attn_norm": (L, D), "blocks/mlp_norm": (L, D),
            "blocks/w_down": (L, F, D), "blocks/w_gate": (L, D, F),
            "blocks/w_up": (L, D, F), "blocks/wk": (L, D, KVH * hd),
            "blocks/wo": (L, H * hd, D), "blocks/wq": (L, D, H * hd),
            "blocks/wv": (L, D, KVH * hd), "embed/tokens": (V, D),
            "lm_head": (D, V), "out_norm": (D,)}


def init_weights(d: dict, seed: int, dtype) -> dict:
    """Weights from the seed: norm gains 1; every matrix normal with
    deviation 0.02, the two that write into the residual stream
    (``wo``, ``w_down``) 0.02 / sqrt(2 L); one key per leaf, split
    from ``key(seed)`` in the order of ``LEAVES``; stored in ``dtype``.
    A flat dict: ``embed/tokens``, ``lm_head``, ``out_norm``, and for
    layer ``i`` ``blocks/<leaf>#i`` — the program stacks a leaf's
    layers on axis 0 and draws the stack in one call, so the stack is
    drawn here and then cut."""
    shapes = leaf_shapes(d)
    keys = jax.random.split(jax.random.key(seed), len(LEAVES))
    out = {}
    for name, k in zip(LEAVES, keys):
        shape = shapes[name]
        if "norm" in name:
            leaf = jnp.ones(shape, dtype)
        else:
            scale = 0.02
            if name in ("blocks/wo", "blocks/w_down"):
                scale = 0.02 / (2.0 * d["L"]) ** 0.5
            leaf = _normal(k, shape, scale, dtype)
        if name.startswith("blocks/"):
            for i, layer in enumerate(_unstack(leaf)):
                out[f"{name}#{i}"] = layer
        else:
            out[name] = leaf
        del leaf
    return out


def layer_weights(weights: dict, i: int) -> dict:
    """Layer ``i``'s leaves under their short names."""
    tail = f"#{i}"
    return {n[len("blocks/"):-len(tail)]: a for n, a in weights.items()
            if n.startswith("blocks/") and n.endswith(tail)}


def stacked_norms(per_leaf: dict) -> dict:
    """Norms of ``blocks/<leaf>#i`` joined to the norm of the stack
    ``blocks/<leaf>``, the leaf as the program holds it."""
    sq = {}
    for n, x in per_leaf.items():
        n = n.split("#")[0]
        sq[n] = sq.get(n, 0.0) + float(x) ** 2
    return {n: math.sqrt(x) for n, x in sq.items()}


@jax.jit
def _unstack(leaf):
    return tuple(leaf[i] for i in range(leaf.shape[0]))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------

def _qdq(x, axis):
    """Round to eight bits along ``axis`` (the contracted one), keep
    float32; the gradient passes straight through."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    """x (..., K) @ w (K, N)."""
    if quant == "int8":
        x, w = _qdq(x, -1), _qdq(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, positions, theta):
    """x (B, T, heads, hd); positions (B, T)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None] * freq
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _attention(q, k, v, keep, quant):
    """q (B, T, KVH, G, hd); k, v (B, T, KVH, hd); keep (B, T, T) bool.
    One key/value head at a time, its scores never kept."""
    scale = q.shape[-1] ** -0.5

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args               # (B,T,G,hd) (B,T,hd) (B,T,hd)
        if quant == "int8":
            qh, kh = _qdq(qh, -1), _qdq(kh, -1)
        s = jnp.einsum("bqgd,bsd->bgqs", qh * scale, kh,
                       precision="highest")
        s = jnp.where(keep[:, None], s, NEG)
        p = jax.nn.softmax(s, -1)
        if quant == "int8":
            p, vh = _qdq(p, -1), _qdq(vh, 1)
        return jnp.einsum("bgqs,bsd->bqgd", p, vh, precision="highest")

    out = jax.lax.map(one, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                            jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2)      # (B, T, KVH, G, hd)


def _layer(x, w, positions, keep, d, quant):
    """One block. ``w``: this layer's seven matrices and two gains, any
    float dtype; everything is computed in float32."""
    w = {k: a.astype(F32) for k, a in w.items()}
    B, T, _ = x.shape
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    h = _rms(x, w["attn_norm"], d["eps"])
    q = _rope(_mm(h, w["wq"], quant).reshape(B, T, H, hd), positions,
              d["theta"]).reshape(B, T, KVH, H // KVH, hd)
    k = _rope(_mm(h, w["wk"], quant).reshape(B, T, KVH, hd), positions,
              d["theta"])
    v = _mm(h, w["wv"], quant).reshape(B, T, KVH, hd)
    a = _attention(q, k, v, keep, quant).reshape(B, T, H * hd)
    x = x + _mm(a, w["wo"], quant)
    h = _rms(x, w["mlp_norm"], d["eps"])
    up = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(up, w["w_down"], quant)


def _keep(positions, segments):
    """Causal within a row, and within a document where rows are packed."""
    T = positions.shape[1]
    keep = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    keep = jnp.broadcast_to(keep, (positions.shape[0], T, T))
    if segments is not None:
        keep = keep & (segments[:, :, None] == segments[:, None, :])
    return keep


def _logits(weights, tokens, positions, segments, d, quant):
    """One whole forward pass under differentiation: each layer's
    inside is computed again in the backward pass, not kept."""
    x = weights["embed/tokens"].astype(F32)[tokens]
    keep = _keep(positions, segments)
    layer = jax.checkpoint(functools.partial(_layer, d=d, quant=quant))
    for i in range(d["L"]):
        x = layer(x, layer_weights(weights, i), positions, keep)
    x = _rms(x, weights["out_norm"].astype(F32), d["eps"])
    return _mm(x, weights["lm_head"].astype(F32), quant)


# ---------------------------------------------------------------------
# serving: logits of whole sequences
# ---------------------------------------------------------------------

def forward_logits(weights: dict, tokens: np.ndarray, d: dict,
                   quant: str | None = None) -> jax.Array:
    """(B, T, V) float32 logits of right-padded rows ``tokens`` (B, T),
    positions 0..T-1. Layer by layer: one jitted call per layer, so
    that only one layer's float32 copy is live."""
    dk = _freeze(d)
    tokens = jnp.asarray(tokens, jnp.int32)
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = _embed(weights["embed/tokens"], tokens)
    for i in range(d["L"]):
        x = _serve_layer(x, layer_weights(weights, i), positions, dk, quant)
    return _head(x, weights["out_norm"], weights["lm_head"], dk, quant)


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _serve_layer(x, w, positions, dk, quant):
    return _layer(x, w, positions, _keep(positions, None), dict(dk), quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, gain, head, dk, quant):
    x = _rms(x, gain.astype(F32), dict(dk)["eps"])
    return _mm(x, head.astype(F32), quant)


@jax.jit
def served_gaps(logits, chosen):
    """For each position, how far the logit of ``chosen`` (B, T; -1
    where nothing was served) lies below the row's best; 0 where
    nothing was served."""
    picked = jnp.take_along_axis(
        logits, jnp.maximum(chosen, 0)[..., None], axis=-1)[..., 0]
    return jnp.where(chosen >= 0, logits.max(-1) - picked, 0.0)


# ---------------------------------------------------------------------
# training: loss, gradient, AdamW
# ---------------------------------------------------------------------

def _row_loss(weights, row, d, z_loss, quant):
    """Mean over the row's labelled tokens of the cross-entropy, plus
    ``z_loss`` times the mean squared log-normaliser."""
    logits = _logits(weights, row["tokens"], row["positions"],
                     row["segments"], d, quant)
    labels = row["labels"]
    valid = labels != IGNORE_INDEX
    lse = jax.scipy.special.logsumexp(logits, -1)
    hit = jnp.take_along_axis(
        logits, jnp.where(valid, labels, 0)[..., None], -1)[..., 0]
    w = valid.astype(F32)
    n = jnp.maximum(w.sum(), 1.0)
    return (((lse - hit) * w).sum() + z_loss * ((lse ** 2) * w).sum()) / n


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(1,))
def _accumulate(weights, acc, row, dk, z_loss, quant):
    loss, g = jax.value_and_grad(_row_loss)(weights, row, dict(dk), z_loss,
                                            quant)
    return loss, jax.tree_util.tree_map(jnp.add, acc, g)


def lr_at(optim: dict, count: int) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then cosine decay
    to a tenth of the peak at ``total_steps`` (optax's
    ``warmup_cosine_decay_schedule``); ``count`` is 0 at the first
    update."""
    peak, warm = optim["learning_rate"], optim["warmup_steps"]
    total = max(optim["total_steps"], warm + 1)
    if count < warm:
        return peak * count / warm
    frac = min(1.0, (count - warm) / (total - warm))
    end = 0.1 * peak
    return end + (peak - end) * 0.5 * (1.0 + math.cos(math.pi * frac))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, m, v, scale, lr, wd, b1, b2, c1, c2):
    g = g * scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    u = (m / c1) / (jnp.sqrt(v / c2) + 1e-8) + wd * p
    return p - lr * u, m, v, jnp.sqrt(jnp.sum(g * g))


def train_steps(weights: dict, batches: list, d: dict, training: dict,
                quant: str | None = None, fault: str | None = None) -> dict:
    """AdamW over ``batches`` (each a dict of (rows, T) arrays; a row
    is one microbatch, the step's gradient the mean of its rows'
    gradients, clipped by its global norm). Returns the losses, the
    per-leaf norms of the first step's clipped gradient and the
    per-leaf norms of the parameters' change after the last step.
    ``weights`` is consumed. ``fault="half-batch"`` plants the fault of
    that name for a reading: each step sees its first rows twice and
    the mean is taken over half of the batch."""
    optim, dk = training["optim"], _freeze(d)
    z = float(training.get("z_loss", 0.0))
    start = {n: np.asarray(a) for n, a in weights.items()}   # host copy
    moments = {}
    losses, first_grad = [], None
    for count, batch in enumerate(batches):
        rows = batch["tokens"].shape[0]
        if fault == "half-batch":
            batch = {k: np.concatenate([v[:rows // 2]] * 2)
                     for k, v in batch.items()}
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        acc = {n: jnp.zeros(a.shape, F32) for n, a in weights.items()}
        row_losses = []
        for r in range(rows):
            row = {k: jnp.asarray(v[r:r + 1]) for k, v in batch.items()}
            loss, acc = _accumulate(weights, acc, row, dk, z, quant)
            row_losses.append(loss)
        losses.append(float(np.mean(jax.device_get(row_losses))))
        sq = sum(float(jnp.sum((g / rows) ** 2)) for g in acc.values())
        gnorm = math.sqrt(sq)
        scale = min(1.0, optim["grad_clip"] / max(gnorm, 1e-30)) / rows
        lr = lr_at(optim, count)
        t = count + 1
        c1, c2 = 1.0 - optim["b1"] ** t, 1.0 - optim["b2"] ** t
        gnorms = {}
        for n in list(weights):
            m, v = moments.pop(n, (None, None))
            if m is None:
                m, v = jnp.zeros_like(acc[n]), jnp.zeros_like(acc[n])
            wd = (0.0 if n.split("#")[0] in NO_DECAY
                  else optim["weight_decay"])
            weights[n], m, v, gn = _adamw_leaf(
                weights[n], acc.pop(n), m, v, scale, lr, wd,
                optim["b1"], optim["b2"], c1, c2)
            gnorms[n] = float(gn)
            moments[n] = (m, v)
        if first_grad is None:
            first_grad = stacked_norms(gnorms)
    change = {n: float(jnp.sqrt(jnp.sum(
        (weights[n] - jnp.asarray(start[n])) ** 2))) for n in weights}
    return {"loss": losses, "first_grad_norm": first_grad,
            "param_change_norm": stacked_norms(change)}
