"""``ops/gated_delta.py``: the delta rule's two forms against its
recurrence, and the decode kernel (interpreted here) against the XLA
step. The kernel compiled for the chip at the published widths, which
interpret mode cannot vouch for, is in ``tests/test_paged_attention.py``
beside the other compiles for a described chip."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.ops import gated_delta as gd
from kubeflow_rm_tpu.ops.attention import kernel_choices

TOL = 2e-5


def _decode(*args, kernel):
    """``decode_step`` with the choice forced: the kernel (interpreted
    here) or the XLA step."""
    with mock.patch.object(gd, "takes_kernel", lambda state: kernel):
        return gd.decode_step(*args)


def _columns(B, T, H, dk, dv, seed=0):
    """q, k (normalised as the layer reads them), v, g <= 0, beta."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, T, H, dk))
    k = jax.random.normal(ks[1], (B, T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (B, T, H))) * 0.3
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta, s):
    out = []
    for t in range(q.shape[1]):
        o, s = gd.step_xla(s, q[:, t], k[:, t], v[:, t], g[:, t],
                           beta[:, t])
        out.append(o)
    return jnp.stack(out, 1), s


@pytest.mark.parametrize("T", [5, 16, 17, 40], ids=["below", "at",
                                                     "one-over", "across"])
def test_chunked_form_equals_the_recurrence(T):
    """Chunks of 16: T below a chunk, at it, one past it, over two."""
    q, k, v, g, beta = _columns(2, T, 3, 8, 4, seed=T)
    s0 = jax.random.normal(jax.random.key(9), (2, 3, 8, 4)) * 0.2
    o, s = gd.chunked(q, k, v, g, beta, s0, chunk=16)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    assert float(jnp.abs(o - want_o).max()) < TOL
    assert float(jnp.abs(s - want_s).max()) < TOL


def test_masked_columns_leave_the_state():
    """Columns with g = beta = 0 (a right-padded tail) leave the state
    the real columns made."""
    q, k, v, g, beta = _columns(1, 24, 2, 8, 4)
    real = jnp.arange(24) < 11
    g = jnp.where(real[None, :, None], g, 0.0)
    beta = jnp.where(real[None, :, None], beta, 0.0)
    s0 = jnp.zeros((1, 2, 8, 4))
    _, s = gd.chunked(q, k, v, g, beta, s0, chunk=8)
    _, want = gd.chunked(q[:, :11], k[:, :11], v[:, :11], g[:, :11],
                         beta[:, :11], s0, chunk=8)
    assert float(jnp.abs(s - want).max()) < TOL


def _state(L=3, B=6, H=16, dk=128, dv=128):
    ks = jax.random.split(jax.random.key(1), 7)
    state = jax.random.normal(ks[0], (L, B, H, dk, dv))
    q = jax.random.normal(ks[1], (B, H, dk)) * 0.1
    k = jax.random.normal(ks[2], (B, H, dk)) * 0.1
    v = jax.random.normal(ks[3], (B, H, dv))
    g = -jnp.abs(jax.random.normal(ks[4], (B, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    return state, (q, k, v, g, beta)


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 1], [0, 0, 0, 0, 0, 1],
                                  [1] * 6], ids=["some", "last", "all"])
def test_kernel_equals_the_xla_step_and_leaves_dead_rows(live):
    state, cols = _state()
    active = jnp.asarray(live, bool)
    o1, s1 = _decode(state, 1, *cols, active, kernel=False)
    o2, s2 = _decode(state, 1, *cols, active, kernel=True)
    assert float(jnp.abs(o1 - o2).max()) < TOL
    assert float(jnp.abs(s1 - s2).max()) < TOL
    dead = np.flatnonzero(~np.asarray(active))
    assert np.array_equal(np.asarray(s2)[:, dead], np.asarray(state)[:, dead])
    assert float(jnp.abs(o2[dead]).max(initial=0.0)) == 0.0
    # the other layers are not touched
    for i in (0, 2):
        assert np.array_equal(np.asarray(s2[i]), np.asarray(state[i]))


def test_no_live_row_leaves_the_whole_state():
    state, cols = _state(B=4)
    o, s = _decode(state, 2, *cols, jnp.zeros((4,), bool), kernel=True)
    assert np.array_equal(np.asarray(s), np.asarray(state))
    assert float(jnp.abs(o).max()) == 0.0


def test_the_grid_walks_the_live_slots_only():
    """The state's index map, run over the grid the call makes, names
    each live slot's tiles of heads once, in the layer given, and no
    dead slot."""
    from jax.experimental import pallas as pl

    seen = {}
    real = pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        seen["grid"] = grid_spec.grid
        seen["map"] = grid_spec.in_specs[-1].index_map
        return real(kernel, grid_spec=grid_spec, **kw)

    state, cols = _state(H=32)
    active = jnp.asarray([0, 1, 0, 0, 1, 0], bool)
    with mock.patch.object(pl, "pallas_call", spy):
        _decode(state, 2, *cols, active, kernel=True)
    steps, tiles = seen["grid"]
    assert (int(steps), tiles) == (2, 32 // gd.HEADS_TILE)
    live = jnp.asarray([1, 4, 0, 0, 0, 0], jnp.int32)
    asked = [tuple(int(x) for x in seen["map"](
        i, j, jnp.asarray([2]), live, jnp.asarray([2])))
        for i in range(2) for j in range(tiles)]
    assert asked == [(2, b, j, 0, 0) for b in (1, 4) for j in range(tiles)]


def test_choice_is_recorded_and_auto_takes_xla_off_the_chip():
    state, cols = _state(B=2)
    with kernel_choices() as chosen:
        gd.decode_step(state, 0, *cols, jnp.ones((2,), bool))
    assert chosen == ["gated_delta_xla"]
    sds = jax.ShapeDtypeStruct
    with mock.patch.object(gd, "computation_devices",
                           lambda *a, **k: ("tpu", 1)), \
            kernel_choices() as chosen:
        assert gd.takes_kernel(sds((3, 32, 32, 128, 128), jnp.float32))
        assert not gd.takes_kernel(sds((3, 4, 4, 16, 8), jnp.float32))
    assert chosen == ["gated_delta_decode", "gated_delta_xla"]
    with mock.patch.object(gd, "computation_devices",
                           lambda *a, **k: ("tpu", 4)):
        assert not gd.takes_kernel(sds((3, 32, 32, 128, 128), jnp.float32))
