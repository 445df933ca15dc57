"""One column of the Mamba-2 recurrence, a decode step's, for the live
slots only.

For each head, with ``S`` a (head_dim, state_size) matrix in float32,
the column's ``x`` (head_dim), step ``dt`` >= 0, decay ``a = dt A`` <= 0
and its group's ``B`` and ``C`` (state_size; ``heads / groups`` heads
share them):

    S_t = e^{a_t} S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t

A column with ``dt = 0`` leaves ``S`` as it was; that is how the layer
masks a dead slot at decode (``models.nemotron_h.mamba_mix``). The many
columns of a prefill are the layer's own chunked scan; this is the
single column, two ways, chosen by ``computation_devices`` as
``ops/gated_delta.py`` chooses (no flag):

- a pallas TPU kernel on a one-device TPU program whose widths tile.
  It takes the layers' **whole stacked state** (layers, slots, heads,
  head_dim, state_size) and the layer's index by scalar prefetch and
  updates it in place (a slice of a stacked operand before a custom
  call is copied by XLA). Its grid walks the **live slots only**: their
  list and number are scalar-prefetched and the grid's first extent is
  that number, so a dead slot's state is neither read nor written. For
  a live slot and a tile of heads (whole groups) it reads ``S`` once,
  computes the decay, the outer product and the read-out in VMEM, and
  writes ``S`` once;
- plain XLA elsewhere, the elementwise step over every row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_rm_tpu.ops.attention import _log_choice, computation_devices

F32 = jnp.float32
#: heads a grid step of the kernel updates, a whole number of groups:
#: at head_dim 64 and state 128, 32 x 32 KiB = 1 MiB of state in and as
#: much out, double-buffered, 4 MiB of VMEM. Swept on a v5e over
#: (5, 32, 128, 64, 128) at 4, 8, 9, 16 and 32 live slots: 16 heads
#: read 1 % faster at 8 and 3-26 % slower at the others, 64 the same or
#: slower
HEADS_TILE = 32


def step_xla(s, x, dt, a, Bm, Cm):
    """One column for every row, in plain XLA: ``s`` (B, H, P, N),
    ``x`` (B, H, P), ``dt`` and ``a`` (B, H), ``Bm`` and ``Cm`` (B, G,
    N), all float32. Returns (y (B, H, P), s')."""
    B, H, P, N = s.shape
    G = Bm.shape[1]
    R = H // G
    s = s.reshape(B, G, R, P, N)
    xb = dt.reshape(B, G, R, 1) * x.reshape(B, G, R, P)
    s = (s * jnp.exp(a).reshape(B, G, R)[..., None, None]
         + xb[..., None] * Bm[:, :, None, None, :])
    y = jnp.sum(s * Cm[:, :, None, None, :], axis=-1)
    return y.reshape(B, H, P), s.reshape(B, H, P, N)


def _tile(H: int, G: int) -> int:
    """The heads a grid step takes: ``HEADS_TILE`` where it is whole
    groups and divides the heads, else all of them (the tests' tiny
    widths, interpreted)."""
    R = H // G
    return (HEADS_TILE if H % HEADS_TILE == 0 and HEADS_TILE % R == 0
            else H)


def takes_kernel(state, n_groups: int) -> bool:
    """Is this call the kernel's: a one-device TPU program whose tile
    of heads is whole groups and whose state fills whole tiles? Says
    which it got (``kernel_choices`` records "mamba_decode" or
    "mamba_xla", once a program)."""
    platform, n_devices = computation_devices(state)
    _, _, H, P, N = state.shape
    if (platform == "tpu" and n_devices == 1 and H % HEADS_TILE == 0
            and HEADS_TILE % (H // n_groups) == 0 and P % 8 == 0
            and N % 128 == 0):
        _log_choice("mamba_decode", False, platform, n_devices,
                    f"heads={H} tile={HEADS_TILE}")
        return True
    _log_choice("mamba_xla", False, platform, n_devices,
                "not a one-device tpu program whose widths tile")
    return False


def decode_step(state, layer: int, x, dt, a, Bm, Cm, active):
    """One column of layer ``layer``'s recurrence for the live rows:
    the kernel where ``takes_kernel`` says so (a test that forces it off
    a TPU program gets the interpreter), the XLA step elsewhere.

    Args:
      state: (L, B, H, P, N) float32, every layer's state, whole.
      layer: static index of the layer.
      x: (B, H, P); dt, a: (B, H); Bm, Cm: (B, G, N), all float32.
      active: (B,) bool, the live rows: a dead row's state comes out
        bit for bit as it went in and its output is 0.

    Returns (y (B, H, P) float32, the whole state updated).
    """
    if takes_kernel(state, Bm.shape[1]):
        return _decode_kernel(
            state, layer, x, dt, a, Bm, Cm, active,
            interpret=computation_devices(state)[0] != "tpu")
    s = state[layer]
    y, new = step_xla(s, x, dt, a, Bm, Cm)
    return (jnp.where(active[:, None, None], y, 0.0),
            state.at[layer].set(jnp.where(active[:, None, None, None],
                                          new, s)))


def _kernel(layer_ref, live_ref, n_ref,                  # scalar prefetch
            x_ref, ad_ref, b_ref, c_ref, s_ref, y_ref, s_out_ref):
    del layer_ref, live_ref
    s = s_ref[...]                                     # (Ht, P, N)
    Ht, P, N = s.shape
    gpt = b_ref.shape[0]                               # groups a tile
    ad = ad_ref[...]                                   # (Ht, 128)
    decay = ad[:, 0:1][:, :, None]                     # (Ht, 1, 1)
    xb = ad[:, 1:2] * x_ref[...]                       # (Ht, P)
    b = b_ref[...]                                     # (gpt, 1, N)
    c = c_ref[...]
    s2 = (s * decay).reshape(gpt, Ht // gpt, P, N) + (
        xb.reshape(gpt, Ht // gpt, P)[..., None] * b[:, None])
    y_ref[...] = jnp.sum(s2 * c[:, None], axis=-1).reshape(Ht, P)
    # the grid holds one step even where no row is live
    s_out_ref[...] = jnp.where(pl.program_id(0) < n_ref[0],
                               s2.reshape(Ht, P, N), s)


def _decode_kernel(state, layer, x, dt, a, Bm, Cm, active, *,
                   interpret=False):
    _, B, H, P, N = state.shape
    G = Bm.shape[1]
    Ht = _tile(H, G)
    gpt = Ht * G // H
    live = jnp.nonzero(active, size=B, fill_value=0)[0].astype(jnp.int32)
    n = jnp.sum(active, dtype=jnp.int32).reshape(1)
    # a head's two scalars in lanes 0 and 1 of a row of its own
    ad = jnp.zeros((B, H, 128), F32).at[..., 0].set(jnp.exp(a)).at[
        ..., 1].set(dt)
    rows = pl.BlockSpec((None, Ht, P),
                        lambda i, j, layer, live, n: (live[i], j, 0))
    groups = pl.BlockSpec((None, gpt, 1, N),
                          lambda i, j, layer, live, n: (live[i], j, 0, 0))
    whole = pl.BlockSpec((None, None, Ht, P, N),
                         lambda i, j, layer, live, n: (layer[0], live[i],
                                                       j, 0, 0))
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n[0], 1), H // Ht),
            in_specs=[rows, pl.BlockSpec(
                (None, Ht, 128),
                lambda i, j, layer, live, n: (live[i], j, 0)),
                groups, groups, whole],
            out_specs=[rows, whole]),
        out_shape=[jax.ShapeDtypeStruct((B, H, P), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the three scalar-prefetched ones: the state
        # (operand 7) is the second output, in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="mamba_decode",
    )(jnp.full((1,), layer, jnp.int32), live, n, x.astype(F32), ad,
      Bm.astype(F32).reshape(B, G, 1, N), Cm.astype(F32).reshape(B, G, 1, N),
      state)
    # a dead row's output block was never written
    return jnp.where(active[:, None, None], y, 0.0), state


__all__ = ["HEADS_TILE", "decode_step", "step_xla", "takes_kernel"]
