"""``ops/expert_ffn.py``: the few-rows kernel against its two references.

Every case routes a few rows over a layer of small squared-ReLU
experts the way ``held_experts_ffn`` does and holds three computations
of the held experts' sum to each other: the pallas kernel (interpreted
here), ``parallel/moe.py``'s loop over the active experts (the plain
path, which the kernel replaces on the chip), and a dense float32 sum
the test writes itself. One case records the blocks the index maps ask
for. The kernel compiled for the chip at the benchmark's widths, which
interpret mode cannot vouch for, is in ``tests/test_paged_attention.py``
beside the other compiles for a described chip (one file, one worker,
one libtpu).
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.ops import expert_ffn
from kubeflow_rm_tpu.ops.attention import kernel_choices
from kubeflow_rm_tpu.parallel import moe

D, d, f, K = 16, 128, 256, 3       # hidden, latent, expert width, top-k
HELD = 8
TOL = 2e-5


def _layer(seed=0, routed=HELD):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (D, routed)),
            jax.random.normal(ks[1], (HELD, d, f)) * 0.2,
            jax.random.normal(ks[2], (HELD, f, d)) * 0.2)


def _rows(N, seed=1):
    ks = jax.random.split(jax.random.key(seed), 2)
    return jax.random.normal(ks[0], (N, D)), jax.random.normal(ks[1], (N, d))


def _bias(chosen, routed=HELD):
    """A router bias under which every row chooses exactly ``chosen``
    (three experts) — or, with None, whatever its scores say."""
    if chosen is None:
        return jnp.zeros((routed,))
    return jnp.zeros((routed,)).at[jnp.asarray(chosen)].set(10.0)


def _dense_sum(h, x, router, bias, up, down, first, live):
    """The held experts' sum written out: float32, every assignment of
    a live row whose expert is held, one by one."""
    idx, w = moe.route_sigmoid_topk(h, router, bias, K, 2.5)
    idx, w = np.asarray(idx), np.asarray(w)
    out = np.zeros((x.shape[0], d), np.float32)
    met = set()
    for n in range(x.shape[0]):
        if live is not None and not bool(live[n]):
            continue
        for e, g in zip(idx[n] - first, w[n]):
            if 0 <= e < HELD:
                met.add(int(e))
                a = np.square(np.maximum(
                    np.asarray(x[n], np.float32) @ np.asarray(up[e]), 0))
                out[n] += g * (a @ np.asarray(down[e]))
    return out, sorted(met)


_KERNEL = expert_ffn.active_experts_ffn
_interpreted = jax.jit(lambda *a: _KERNEL(*a, interpret=True))


def _forced(impl):
    """``held_experts_ffn`` with the few-rows choice forced: the
    kernel interpreted, or the loop."""
    if impl == "loop":
        return mock.patch.object(expert_ffn, "takes_kernel",
                                 lambda x, up: False)
    return mock.patch.multiple(
        expert_ffn, takes_kernel=lambda x, up: True,
        active_experts_ffn=_interpreted)


# active experts: none of those held (the rows choose experts of
# another share), one (all rows agree on three, one of them held),
# a few (all rows agree on three held), whatever the scores say (all
# eight meet a token at 32 rows and more)
CHOICES = {
    "none": dict(routed=16, first=8, chosen=(1, 4, 6), n_active=0),
    "one": dict(routed=16, first=0, chosen=(5, 9, 12), n_active=1),
    "few": dict(routed=8, first=0, chosen=(1, 4, 6), n_active=3),
    "all": dict(routed=8, first=0, chosen=None, n_active=None),
    "first>0": dict(routed=24, first=8, chosen=None, n_active=None),
}


@pytest.mark.parametrize("dead", [False, True], ids=["live", "dead-rows"])
@pytest.mark.parametrize("choice", sorted(CHOICES))
@pytest.mark.parametrize("N", [8, 32, 64])
def test_kernel_matches_the_loop_and_a_dense_float32_sum(N, choice, dead):
    c = CHOICES[choice]
    router, up, down = _layer(routed=c["routed"])
    h, x = _rows(N)
    bias = _bias(c["chosen"], c["routed"])
    live = (jnp.arange(N) % 3 != 1) if dead else None
    outs = {}
    for impl in ("kernel", "loop"):
        with _forced(impl):
            outs[impl], (n, active) = moe.held_experts_ffn(
                h, router, bias, up, down, c["first"], K, 2.5,
                expert_in=x, live=live)
    want, met = _dense_sum(h, x, router, bias, up, down, c["first"], live)
    assert int(active) == len(met)
    if c["n_active"] is not None:
        assert len(met) == c["n_active"]
    elif N >= 32 and c["first"] == 0:
        assert len(met) == HELD
    scale = max(1.0, float(np.abs(want).max()))
    assert float(jnp.abs(outs["kernel"] - outs["loop"]).max()) <= TOL * scale
    assert float(np.abs(outs["kernel"] - want).max()) <= TOL * scale
    if dead:
        assert float(jnp.abs(outs["kernel"][1::3]).max()) == 0.0
    if not met:
        assert float(jnp.abs(outs["kernel"]).max()) == 0.0


@pytest.mark.parametrize("N", [5, 20], ids=["5-rows", "20-rows"])
def test_rows_are_padded_to_the_sublane_tile_in_bf16(N):
    """A row count that fills no sublane tile (a bucket of a few
    tokens) in the cell's dtype: the padding rows weigh 0 and the
    answer is the loop's to float32 rounding."""
    router, up, down = _layer()
    h, x = _rows(N)
    x, up, down = (a.astype(jnp.bfloat16) for a in (x, up, down))
    outs = {}
    for impl in ("kernel", "loop"):
        with _forced(impl):
            outs[impl], _ = moe.held_experts_ffn(
                h, router, _bias(None), up, down, 0, K, 2.5, expert_in=x)
    assert outs["kernel"].shape == (N, d)
    assert outs["kernel"].dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    assert np.abs(f32(outs["kernel"]) - f32(outs["loop"])).max() \
        <= 1e-2 * np.abs(f32(outs["loop"])).max()


def test_only_the_active_experts_blocks_are_asked_for():
    """The index maps of ``up`` and ``down``, run over the grid the
    call makes: every block they name belongs to an expert that met a
    token, each of those once a tile, in ascending order — an expert
    nobody chose is neither fetched nor stepped over."""
    from jax.experimental import pallas as pl

    seen = {}
    real = pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        seen["grid"] = grid_spec.grid
        seen["maps"] = [s.index_map for s in grid_spec.in_specs[2:]]
        seen["blocks"] = [s.block_shape for s in grid_spec.in_specs[2:]]
        return real(kernel, grid_spec=grid_spec, **kw)

    held, wide = 8, 3 * expert_ffn.TILE_F
    active = jnp.asarray([2, 5, 7, 0, 0, 0, 0, 0], jnp.int32)
    n = jnp.int32(3)
    dense = jnp.zeros((held, 8)).at[jnp.asarray([2, 5, 7])].set(1.0)
    with mock.patch.object(pl, "pallas_call", spy):
        expert_ffn.active_experts_ffn(
            jnp.ones((8, 128)), dense, active, n,
            jnp.zeros((held, 128, wide)), jnp.zeros((held, wide, 128)),
            interpret=True)
    steps, tiles = seen["grid"]
    assert (int(steps), tiles) == (3, 3)
    assert seen["blocks"] == [(None, 128, expert_ffn.TILE_F),
                              (None, expert_ffn.TILE_F, 128)]
    asked = [[tuple(int(v) for v in m(i, j, active, n[None]))
              for i in range(int(steps)) for j in range(tiles)]
             for m in seen["maps"]]
    assert asked[0] == [(e, 0, j) for e in (2, 5, 7) for j in range(3)]
    assert asked[1] == [(e, j, 0) for e in (2, 5, 7) for j in range(3)]


def test_choice_is_recorded_and_auto_takes_the_loop_off_the_chip():
    """On the CPU ``held_experts_ffn`` takes the loop and says so; a
    one-device TPU program whose widths tile takes the kernel, one
    whose widths do not (the tests' ``d`` 8, ``f`` 12) the loop; rows
    above ``_FEW_ROWS`` consult nobody."""
    router, up, down = _layer()
    h, x = _rows(8)
    with kernel_choices() as chosen:
        moe.held_experts_ffn(h, router, _bias(None), up, down, 0, K, 2.5,
                             expert_in=x)
    assert chosen == ["held_experts_xla"]
    on_chip = mock.patch.object(expert_ffn, "computation_devices",
                                lambda *a, **k: ("tpu", 1))
    sds = jax.ShapeDtypeStruct
    with on_chip, kernel_choices() as chosen:
        assert expert_ffn.takes_kernel(sds((32, 1024), jnp.bfloat16),
                                       sds((128, 1024, 2688), jnp.bfloat16))
        assert not expert_ffn.takes_kernel(sds((32, 8), jnp.float32),
                                           sds((4, 8, 12), jnp.float32))
    assert chosen == ["held_experts", "held_experts_xla"]
    four = mock.patch.object(expert_ffn, "computation_devices",
                             lambda *a, **k: ("tpu", 4))
    with four:
        assert not expert_ffn.takes_kernel(
            sds((32, 1024), jnp.bfloat16),
            sds((128, 1024, 2688), jnp.bfloat16))
    h, x = _rows(moe._FEW_ROWS + 1)
    with on_chip, kernel_choices() as chosen:
        moe.held_experts_ffn(h, router, _bias(None), up, down, 0, K, 2.5,
                             expert_in=x)
    assert chosen == []
    assert expert_ffn.tile_of(2688) == 896 and expert_ffn.tile_of(256) == 256
