"""The held experts' sum over a few rows, each active expert's two
matrices streamed once.

A decode step (or a prompt's bucket) brings a few rows to a
layer of squared-ReLU experts ``up`` (held, d, f) and ``down`` (held,
f, d), or of SwiGLU experts with a third matrix ``gate`` (held, d, f)
(``down(silu(x gate) * x up)``); some of the held experts met a token,
and only their matrices must move. Two implementations of one function, chosen the way
``ops/paged_attention.py`` chooses (by what ``computation_devices``
observes; no flag, no environment variable):

- **pallas TPU kernel** on a one-device TPU program whose widths
  tile. The list of active experts and their number are
  scalar-prefetched; the grid walks (position in the list, tile of
  ``f``) and each block index map picks expert ``active[i]``'s tile
  out of the layer's whole array where it lies, so nothing of a
  weight's size is sliced or copied in XLA before the call. Pallas's
  double buffering fetches the next tile, the next expert's first
  among them, while this one multiplies: the matrices arrive back to
  back, which is the whole gain over a loop whose every iteration
  starts its own reads. The grid's first extent is the number of
  active experts, so an expert nobody chose costs neither a copy nor
  a step.
- **plain XLA** elsewhere (CPU tests, a sharded call):
  ``parallel/moe.py``'s ``_active_experts_loop``, the kernel's
  reference.

All rows multiply with every active expert and a row that did not
choose it weighs 0, as in the loop: bf16 products summed in float32,
the activation cast to the rows' dtype between the two matmuls, the
weighted sum in float32 in the experts' ascending order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_rm_tpu.ops.attention import _log_choice, computation_devices

#: columns of ``f`` a grid step multiplies: an expert's ``(d, TILE_F)``
#: and ``(TILE_F, d)`` tiles, two of each in flight (7.3 MB at the
#: Nemotron cut's widths, inside the default scoped VMEM). The largest
#: multiple of 128 that divides ``f`` and is not above this is taken.
#: Measured on the v5e at 32 rows, 1024 x 2688, us an active expert at
#: 20 / 37 / 60 / 118 active of 128 held: 384 15.3 / 15.0 / 14.8 / 14.7,
#: 896 15.4 / 15.0 / 14.8 / 14.7, the whole 2688 (which needs a raised
#: VMEM limit) 15.5 / 15.0 / 14.8 / 14.7; the loop in XLA 21.0 / 20.7 /
#: 20.5 / 20.4. The tile does not matter; a grid of all held experts
#: with the idle steps guarded costs 16.8 / 15.7 / 15.2 / 14.7, which
#: is why the extent is dynamic (PERF.md section 6, PR 41).
TILE_F = 896


def tile_of(f: int, cap: int = TILE_F) -> int:
    """The tile of ``f`` the kernel takes: the largest multiple of 128
    dividing ``f`` that is not above ``cap``."""
    return max(t for t in range(128, max(128, min(f, cap)) + 1, 128)
               if f % t == 0)


def gated_cap(d: int) -> int:
    """``TILE_F`` for a SwiGLU expert of width ``d``: the columns whose
    three tiles hold what two of ``TILE_F`` hold at ``d`` = 1024 (3.1
    MB a grid step at Qwen3-Next's 2048 x 512 experts: tile 256)."""
    return 2 * 1024 * TILE_F // (3 * d)


def takes_kernel(x, up) -> bool:
    """Is this call the kernel's: a one-device TPU program whose ``d``
    and ``f`` fill whole lanes? Says which it got (``kernel_choices``
    records "held_experts" or "held_experts_xla", once a program)."""
    platform, n_devices = computation_devices(x)
    _, d, f = up.shape
    if (platform == "tpu" and n_devices == 1 and d % 128 == 0
            and f % 128 == 0):
        _log_choice("held_experts", False, platform, n_devices,
                    f"rows={x.shape[0]} tile_f={tile_of(f)}")
        return True
    _log_choice("held_experts_xla", False, platform, n_devices,
                "not a one-device tpu program whose widths tile")
    return False


def _kernel(active_ref, n_ref,                         # scalar prefetch
            x_ref, w_ref, up_ref, down_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the grid holds one expert even where none is active
    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        a = jnp.dot(x, up_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        a = jnp.square(jnp.maximum(a, 0.0)).astype(x.dtype)
        y = jnp.dot(a, down_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        # the rows' weights for this expert: its column of the table
        w = w_ref[...]                                 # (N, held) f32
        col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        w = jnp.sum(jnp.where(col == active_ref[i], w, 0.0), axis=1,
                    keepdims=True)
        o_ref[...] += w * y


def _gated_kernel(active_ref, n_ref,                   # scalar prefetch
                  x_ref, w_ref, gate_ref, up_ref, down_ref, o_ref):
    """``_kernel`` for SwiGLU experts: the gate's tile beside ``up``'s."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        a = jnp.dot(x, up_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * a).astype(x.dtype)
        y = jnp.dot(a, down_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        w = w_ref[...]
        col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        w = jnp.sum(jnp.where(col == active_ref[i], w, 0.0), axis=1,
                    keepdims=True)
        o_ref[...] += w * y


def active_experts_ffn(x, dense, active, n, up, down, *, gate=None,
                       interpret: bool = False):
    """``sum_e dense[e][:, None] * (square(relu(x @ up[e])) @ down[e])``
    over the experts ``active[:n]``; with ``gate`` the SwiGLU experts'
    ``silu(x @ gate[e]) * (x @ up[e])`` in the squared ReLU's place,
    the three tiles of an expert streamed back to back.

    Args:
      x: (N, d) rows.
      dense: (held, N) float32, row n's weight for each held expert, 0
        where it did not choose it.
      active: (held,) int32 the experts that met a token, ascending,
        then anything; n: () int32 their number (0 gives zeros).
      up, down: (held, d, f) and (held, f, d), whole.
      gate: None, or (held, d, f) whole: SwiGLU experts.
      interpret: run the kernel in the pallas interpreter (off-TPU).

    Returns (N, d) float32.
    """
    N, d = x.shape
    held, _, f = up.shape
    tf = tile_of(f) if gate is None else tile_of(f, gated_cap(d))
    # whole sublane tiles of the rows' (packed) dtype; a padding row
    # weighs 0 with every expert
    sub = 8 * (4 // jnp.dtype(x.dtype).itemsize)
    Np = -(-N // sub) * sub
    x = jnp.pad(x, ((0, Np - N), (0, 0)))
    w = jnp.pad(dense.T, ((0, Np - N), (0, 0)))
    n = jnp.reshape(n, (1,)).astype(jnp.int32)

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, j, *_: (0,) * len(shape))

    col_tile = pl.BlockSpec((None, d, tf),
                            lambda i, j, active, n: (active[i], 0, j))
    weights = (up, down) if gate is None else (gate, up, down)
    out = pl.pallas_call(
        _kernel if gate is None else _gated_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(jnp.maximum(n[0], 1), f // tf),
            in_specs=[
                full(Np, d), full(Np, held),
                *[col_tile] * (len(weights) - 1),
                pl.BlockSpec((None, tf, d),
                             lambda i, j, active, n: (active[i], j, 0)),
            ],
            out_specs=full(Np, d)),
        out_shape=jax.ShapeDtypeStruct((Np, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="held_experts_ffn",
    )(active.astype(jnp.int32), n, x, w, *weights)
    return out[:N]


__all__ = ["TILE_F", "active_experts_ffn", "gated_cap", "takes_kernel",
           "tile_of"]
