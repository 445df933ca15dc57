"""``ops/mamba_decode.py``: the Mamba-2 decode kernel (interpreted
here) against the XLA step. The kernel compiled for the chip inside the
hybrid family's decode step, which interpret mode cannot vouch for, is
in ``tests/test_paged_attention.py`` beside the other compiles for a
described chip."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.ops import mamba_decode as md
from kubeflow_rm_tpu.ops.attention import kernel_choices

TOL = 2e-5


def _decode(*args, kernel):
    """``decode_step`` with the choice forced: the kernel (interpreted
    here) or the XLA step."""
    with mock.patch.object(md, "takes_kernel", lambda state, g: kernel):
        return md.decode_step(*args)


def _state(L=3, B=6, H=64, P=16, N=128, G=8):
    """A stacked state and one column's inputs: ``G`` = 8 groups of 8
    heads, so a tile of 32 heads spans four groups, and a slot two
    tiles."""
    ks = jax.random.split(jax.random.key(1), 7)
    state = jax.random.normal(ks[0], (L, B, H, P, N))
    x = jax.random.normal(ks[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H)) - 2.0)
    a = dt * -jnp.exp(jax.random.normal(ks[3], (H,)))
    Bm = jax.random.normal(ks[4], (B, G, N)) * 0.1
    Cm = jax.random.normal(ks[5], (B, G, N)) * 0.1
    return state, (x, dt, a, Bm, Cm)


def test_the_xla_step_is_the_recurrence_head_by_head():
    """Each head reads its own group's B and C: the step against the
    equations written a head at a time."""
    state, (x, dt, a, Bm, Cm) = _state(L=1, B=2, H=8, P=4, N=8, G=2)
    y, s = md.step_xla(state[0], x, dt, a, Bm, Cm)
    for h in range(8):
        g = h // 4
        want = (state[0][:, h] * jnp.exp(a[:, h])[:, None, None]
                + (dt[:, h, None] * x[:, h])[:, :, None] * Bm[:, g, None, :])
        assert float(jnp.abs(s[:, h] - want).max()) < TOL
        got_y = jnp.einsum("bpn,bn->bp", want, Cm[:, g], precision="highest")
        assert float(jnp.abs(y[:, h] - got_y).max()) < TOL


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 1], [0, 0, 0, 0, 0, 1],
                                  [1] * 6], ids=["some", "last", "all"])
def test_kernel_equals_the_xla_step_and_leaves_dead_rows(live):
    state, cols = _state()
    active = jnp.asarray(live, bool)
    y1, s1 = _decode(state, 1, *cols, active, kernel=False)
    y2, s2 = _decode(state, 1, *cols, active, kernel=True)
    assert float(jnp.abs(y1 - y2).max()) < TOL
    assert float(jnp.abs(s1 - s2).max()) < TOL
    dead = np.flatnonzero(~np.asarray(active))
    assert np.array_equal(np.asarray(s2)[:, dead], np.asarray(state)[:, dead])
    assert np.array_equal(np.asarray(s1)[:, dead], np.asarray(state)[:, dead])
    assert float(jnp.abs(y2[dead]).max(initial=0.0)) == 0.0
    # the live rows moved, and the other layers are not touched
    assert not np.array_equal(np.asarray(s2[1])[np.asarray(active)],
                              np.asarray(state[1])[np.asarray(active)])
    for i in (0, 2):
        assert np.array_equal(np.asarray(s2[i]), np.asarray(state[i]))


def test_no_live_row_leaves_the_whole_state():
    state, cols = _state(B=4)
    y, s = _decode(state, 2, *cols, jnp.zeros((4,), bool), kernel=True)
    assert np.array_equal(np.asarray(s), np.asarray(state))
    assert float(jnp.abs(y).max()) == 0.0


def test_the_grid_walks_the_live_slots_only():
    """The state's index map, run over the grid the call makes, names
    each live slot's tiles of heads once, in the layer given, and no
    dead slot; a tile's B and C are its own groups'."""
    from jax.experimental import pallas as pl

    seen = {}
    real = pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        seen["grid"] = grid_spec.grid
        seen["state"] = grid_spec.in_specs[-1].index_map
        seen["groups"] = grid_spec.in_specs[2]
        return real(kernel, grid_spec=grid_spec, **kw)

    state, cols = _state(H=128)
    active = jnp.asarray([0, 1, 0, 0, 1, 0], bool)
    with mock.patch.object(pl, "pallas_call", spy):
        _decode(state, 2, *cols, active, kernel=True)
    steps, tiles = seen["grid"]
    assert (int(steps), tiles) == (2, 128 // md.HEADS_TILE)
    live = jnp.asarray([1, 4, 0, 0, 0, 0], jnp.int32)
    at = (jnp.asarray([2]), live, jnp.asarray([2]))
    asked = [tuple(int(v) for v in seen["state"](i, j, *at))
             for i in range(2) for j in range(tiles)]
    assert asked == [(2, b, j, 0, 0) for b in (1, 4) for j in range(tiles)]
    # 16 heads a group: a tile of 32 is groups 2j and 2j + 1
    groups = seen["groups"]
    assert groups.block_shape[1] == md.HEADS_TILE // 16 == 2
    assert [tuple(int(v) for v in groups.index_map(1, j, *at))
            for j in range(tiles)] == [(4, j, 0, 0) for j in range(tiles)]


def test_choice_is_recorded_and_auto_takes_xla_off_the_chip():
    state, cols = _state(B=2)
    with kernel_choices() as chosen:
        md.decode_step(state, 0, *cols, jnp.ones((2,), bool))
    assert chosen == ["mamba_xla"]
    sds = jax.ShapeDtypeStruct
    cell = sds((5, 32, 128, 64, 128), jnp.float32)
    with mock.patch.object(md, "computation_devices",
                           lambda *a, **k: ("tpu", 1)), \
            kernel_choices() as chosen:
        assert md.takes_kernel(cell, 8)
        # the tests' tiny widths: 8 heads, head 8, state 16
        assert not md.takes_kernel(sds((2, 4, 8, 8, 16), jnp.float32), 2)
        # a group wider than the tile
        assert not md.takes_kernel(cell, 2)
    assert chosen == ["mamba_decode", "mamba_xla", "mamba_xla"]
    with mock.patch.object(md, "computation_devices",
                           lambda *a, **k: ("tpu", 4)):
        assert not md.takes_kernel(cell, 8)
