"""The gated delta rule of a Gated DeltaNet layer, two ways.

For each value head, with ``S`` a (dk, dv) matrix in float32 and the
column's ``q``, ``k`` (dk), ``v`` (dv), log decay ``g`` <= 0 and
strength ``beta`` in (0, 1):

    S_t = e^{g_t} S_{t-1} + k_t [beta_t (v_t - e^{g_t} S_{t-1}^T k_t)]^T
    o_t = S_t^T q_t

A column with ``g = 0`` and ``beta = 0`` leaves ``S`` as it was: that
is how a caller masks a column (a right-padded bucket's tail, a dead
slot at decode).

- **Many columns** (``chunked``, prefill): plain ``jax.numpy`` in
  float32 at ``highest`` precision, chunks of ``CHUNK``. Inside a
  chunk the rule is the WY/UT form: with ``gamma`` the cumulative log
  decay and ``A = strictly_lower(beta_i e^{gamma_i - gamma_j} k_i . k_j)``
  the chunk's updates ``U`` solve the unit lower-triangular system
  ``(I + A) U = beta V - (beta e^gamma K) S_0``, one triangular solve
  for every chunk and head at once; between chunks a scan carries
  ``S`` (as ``models.nemotron_h._chunked_scan`` does for Mamba-2).
- **One column** (``decode_step``, a decode step's): a pallas TPU
  kernel on a one-device TPU program whose widths tile, plain XLA
  elsewhere, chosen by ``computation_devices`` as
  ``ops/expert_ffn.py`` chooses (no flag). The kernel takes the
  layer's **whole stacked state** (layers, slots, heads, dk, dv) and
  the layer's index by scalar prefetch and updates it in place (a
  slice of a stacked operand before a custom call is copied by XLA:
  67 MB a layer a step at Qwen3-Next's widths). Its grid walks the
  **live slots only**: their list and number are scalar-prefetched
  and the grid's first extent is that number, so a dead slot's state
  is neither read nor written. For a live slot and a tile of heads it
  reads ``S`` once, computes ``S^T k``, the update and ``S_t^T q`` in
  VMEM, and writes ``S`` once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_rm_tpu.ops.attention import _log_choice, computation_devices

F32 = jnp.float32
#: columns a chunk of the prefill's form holds (the published kernels')
CHUNK = 64
#: value heads a grid step of the decode kernel updates: 8 x 64 KiB of
#: state in and out, double-buffered, 2 MiB of VMEM
HEADS_TILE = 8


def _ein(spec, *ops):
    return jnp.einsum(spec, *ops, precision="highest")


def chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The rule over T columns at once.

    Args:
      q, k: (B, T, H, dk) float32, as the rule reads them (normalised
        and scaled by the caller).
      v: (B, T, H, dv) float32.
      g, beta: (B, T, H) float32.
      state: (B, H, dk, dv) float32, the state entering the columns.

    Returns (o (B, T, H, dv) float32, the state after the last column).
    T is padded to whole chunks with columns of ``g = beta = 0``.
    """
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc = (T + pad) // C
    # (B, H, nc, C, ...): a chunk's columns next to last
    q, k, v = (a.reshape(B, nc, C, H, -1).transpose(0, 3, 1, 2, 4)
               for a in (q, k, v))
    g, beta = (a.reshape(B, nc, C, H).transpose(0, 3, 1, 2)
               for a in (g, beta))
    gamma = jnp.cumsum(g, axis=-1)                          # (B,H,nc,C)
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    diff = gamma[..., :, None] - gamma[..., None, :]
    a = jnp.where(lower, beta[..., None] * jnp.exp(
        jnp.where(lower, diff, 0.0)) * _ein("...id,...jd->...ij", k, k),
        0.0)
    # (I + A) [W_v | W_k] = [beta V | beta e^gamma K]
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(gamma))[..., None] * k], -1)
    w = jax.lax.linalg.triangular_solve(
        a + jnp.eye(C, dtype=F32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w_v, w_k = w[..., :dv], w[..., dv:]
    causal = jnp.tril(jnp.ones((C, C), bool))
    p = jnp.where(causal, _ein("...id,...jd->...ij", q, k) * jnp.exp(
        jnp.where(causal, diff, 0.0)), 0.0)                 # (...,C,C)
    q_in = jnp.exp(gamma)[..., None] * q                    # read S_0
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None] * k
    whole = jnp.exp(gamma[..., -1])                         # (B,H,nc)

    def step(s, c):
        w_v, w_k, p, q_in, to_end, whole = c
        u = w_v - _ein("bhck,bhkv->bhcv", w_k, s)
        o = _ein("bhck,bhkv->bhcv", q_in, s) + _ein("bhij,bhjv->bhiv", p, u)
        s = whole[..., None, None] * s + _ein("bhck,bhcv->bhkv", to_end, u)
        return s, o

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(x, 2, 0) for x in
                           (w_v, w_k, p, q_in, to_end, whole)))
    # (nc, B, H, C, dv) -> (B, T, H, dv)
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, nc * C, H, dv)
    return o[:, :T], state


def step_xla(s, q, k, v, g, beta):
    """One column for every row, in plain XLA: ``s`` (B, H, dk, dv),
    ``q``, ``k`` (B, H, dk), ``v`` (B, H, dv), ``g``, ``beta`` (B, H),
    all float32. Returns (o (B, H, dv), s')."""
    s = s * jnp.exp(g)[..., None, None]
    kv = jnp.sum(s * k[..., :, None], axis=-2)
    u = beta[..., None] * (v - kv)
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def takes_kernel(state) -> bool:
    """Is this call the kernel's: a one-device TPU program whose heads
    fill whole tiles and whose state fills whole lanes? Says which it
    got (``kernel_choices`` records "gated_delta_decode" or
    "gated_delta_xla", once a program)."""
    platform, n_devices = computation_devices(state)
    _, _, H, dk, dv = state.shape
    if (platform == "tpu" and n_devices == 1 and H % HEADS_TILE == 0
            and dk % 128 == 0 and dv % 128 == 0):
        _log_choice("gated_delta_decode", False, platform, n_devices,
                    f"heads={H} tile={HEADS_TILE}")
        return True
    _log_choice("gated_delta_xla", False, platform, n_devices,
                "not a one-device tpu program whose widths tile")
    return False


def decode_step(state, layer: int, q, k, v, g, beta, active):
    """One column of layer ``layer``'s rule for the live rows: the
    kernel where ``takes_kernel`` says so (a test that forces it off a
    TPU program gets the interpreter), the XLA step elsewhere.

    Args:
      state: (L, B, H, dk, dv) float32, every layer's state, whole.
      layer: static index of the layer.
      q, k: (B, H, dk); v: (B, H, dv); g, beta: (B, H) float32.
      active: (B,) bool, the live rows: a dead row's state comes out
        bit for bit as it went in and its output is 0.

    Returns (o (B, H, dv) float32, the whole state updated).
    """
    if takes_kernel(state):
        return _decode_kernel(
            state, layer, q, k, v, g, beta, active,
            interpret=computation_devices(state)[0] != "tpu")
    s = state[layer]
    o, new = step_xla(s, q, k, v, g, beta)
    return (jnp.where(active[:, None, None], o, 0.0),
            state.at[layer].set(jnp.where(active[:, None, None, None],
                                          new, s)))


def _kernel(layer_ref, live_ref, n_ref,                  # scalar prefetch
            q_ref, k_ref, v_ref, gb_ref, s_ref, o_ref, s_out_ref):
    del layer_ref, live_ref
    s = s_ref[...]                                     # (Ht, dk, dv)
    k = k_ref[...]                                     # (Ht, dk)
    q = q_ref[...]
    gb = gb_ref[...]                                   # (Ht, 128)
    decay = gb[:, 0:1][:, :, None]                     # (Ht, 1, 1)
    beta = gb[:, 1:2]                                  # (Ht, 1)
    kc = k[:, :, None]                                 # (Ht, dk, 1)
    s1 = s * decay
    kv = jnp.sum(s1 * kc, axis=1)                      # (Ht, dv)
    u = beta * (v_ref[...] - kv)
    s2 = s1 + kc * u[:, None, :]
    o_ref[...] = jnp.sum(s2 * q[:, :, None], axis=1)
    # the grid holds one step even where no row is live
    s_out_ref[...] = jnp.where(pl.program_id(0) < n_ref[0], s2, s)


def _decode_kernel(state, layer, q, k, v, g, beta, active, *,
                   interpret=False):
    L, B, H, dk, dv = state.shape
    live = jnp.nonzero(active, size=B, fill_value=0)[0].astype(jnp.int32)
    n = jnp.sum(active, dtype=jnp.int32).reshape(1)
    # a head's two scalars in lanes 0 and 1 of a row of its own
    gb = jnp.zeros((B, H, 128), F32).at[..., 0].set(jnp.exp(g)).at[
        ..., 1].set(beta)
    # (a head count no tile divides, the tests' tiny widths
    # interpreted, goes in whole)
    Ht = HEADS_TILE if H % HEADS_TILE == 0 else H

    def rows(width):
        return pl.BlockSpec((None, Ht, width),
                            lambda i, j, layer, live, n: (live[i], j, 0))

    whole = pl.BlockSpec((None, None, Ht, dk, dv),
                         lambda i, j, layer, live, n: (layer[0], live[i],
                                                       j, 0, 0))
    o, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n[0], 1), H // Ht),
            in_specs=[rows(dk), rows(dk), rows(dv), rows(128), whole],
            out_specs=[rows(dv), whole]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the three scalar-prefetched ones: the state
        # (operand 7) is the second output, in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="gated_delta_decode",
    )(jnp.full((1,), layer, jnp.int32), live, n, q.astype(F32),
      k.astype(F32), v.astype(F32), gb, state)
    # a dead row's output block was never written
    return jnp.where(active[:, None, None], o, 0.0), state


__all__ = ["CHUNK", "HEADS_TILE", "chunked", "decode_step", "step_xla",
           "takes_kernel"]
