"""``ops/paged_attention.py``: the decode kernel against its two references.

Every case builds a small pool the way the engine leaves one — a
slot's token ``t`` at strip offset ``t`` with position ``t``, the
blocks a slot does not own holding other slots' or stale content —
and holds three computations of one token's attention to each other:
the pallas kernel (interpreted here), the XLA block-table path, and
``dot_product_attention`` over a strip the test gathers itself with
numpy. The last test compiles the kernel for the chip at the
benchmark's widths, which interpret mode cannot vouch for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.models.generate import _UNFILLED
from kubeflow_rm_tpu.ops import dot_product_attention
from kubeflow_rm_tpu.ops import paged_attention as pa
from kubeflow_rm_tpu.ops.attention import kernel_choices

U = int(_UNFILLED)
L, NB, BS, MAXB, HD = 2, 24, 4, 4, 16
S = MAXB * BS
LAYER = 1


class _Pool:
    """A pool under construction: ``seat`` gives a slot fresh blocks
    for ``n`` cached tokens (plus the one being decoded), ``share``
    points a table entry at another slot's block."""

    def __init__(self, rng, kvh, dtype):
        self.k = rng.normal(size=(L, NB, BS, kvh, HD)).astype(np.float32)
        self.v = rng.normal(size=(L, NB, BS, kvh, HD)).astype(np.float32)
        # NULL and SINK as the engine keeps them; everything else
        # starts as a block some earlier request left behind
        self.k[:, 0] = self.v[:, 0] = 0
        self.pos = np.full((NB, BS), U, np.int64)
        self.tables, self.lengths, self.active = [], [], []
        self.dtype = dtype
        self._next = 2

    def seat(self, n, *, active=True, stale=None):
        """``stale`` fills the positions past ``n`` in the slot's
        last block: ``"unfilled"`` as ``paged_install`` leaves them,
        ``"later"`` with positions after the query's, as the tail of
        a block another request went on writing."""
        row = [0] * MAXB
        for j in range(-(-(n + 1) // BS)):
            row[j] = self._next
            for t in range(BS):
                at = j * BS + t
                if at < n:
                    self.pos[self._next, t] = at
                elif stale == "later":
                    self.pos[self._next, t] = at + 7
            self._next += 1
        self.tables.append(row)
        self.lengths.append(n)
        self.active.append(active)
        return len(self.tables) - 1

    def share(self, slot, j, other):
        self.tables[slot][j] = self.tables[other][j]

    def arrays(self):
        as_dt = lambda a: jnp.asarray(a, self.dtype)  # noqa: E731
        return (as_dt(self.k), as_dt(self.v),
                jnp.asarray(self.pos, jnp.int32),
                jnp.asarray(self.tables, jnp.int32),
                jnp.asarray(self.lengths, jnp.int32),
                jnp.asarray(self.active))


def _length_one(p):
    p.seat(0)                      # attends to itself alone
    p.seat(1)


def _block_boundary(p):
    p.seat(BS)                     # the token opens a new block
    p.seat(2 * BS - 1)             # the token fills a block's last row


def _full_strip(p):
    p.seat(S - 1)
    p.seat(3)


def _inactive_beside_active(p):
    p.seat(5)
    p.seat(9, active=False)
    p.seat(0, active=False)
    p.seat(6)


def _shared_block(p):
    a = p.seat(2 * BS + 1)
    b = p.seat(BS + 2)
    p.share(b, 0, a)               # one prompt's first block, adopted


def _recycled_block(p):
    p.seat(5, stale="unfilled")    # stale K/V under _UNFILLED
    p.seat(6, stale="later")       # stale K/V under later positions


def _null_entries(p):
    p.seat(2)                      # three of four entries NULL
    p.seat(BS + 1)


SLOTS = 4
_attend = jax.jit(pa.paged_decode_attention, static_argnames=("impl",))

SCENES = {f.__name__.strip("_"): f for f in (
    _length_one, _block_boundary, _full_strip, _inactive_beside_active,
    _shared_block, _recycled_block, _null_entries)}


@pytest.mark.parametrize("scene", sorted(SCENES))
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_matches_block_table_path_and_gathered_strip(
        dtype, group, scene):
    kvh = 2
    H = kvh * group
    rng = np.random.default_rng(
        sorted(SCENES).index(scene) * 10 + group)
    pool = _Pool(rng, kvh, dtype)
    SCENES[scene](pool)
    while len(pool.tables) < SLOTS:     # one shape, one compilation
        pool.seat(0, active=False)
    pk, pv, pos, tables, lengths, active = pool.arrays()
    B = tables.shape[0]
    rows = np.arange(B)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    q, k_new, v_new = draw(B, H, HD), draw(B, kvh, HD), draw(B, kvh, HD)
    positions_q = jnp.where(active, lengths, U)
    kv_positions = pos[tables].reshape(B, S).at[rows, lengths].set(
        positions_q)
    kw = dict(positions_q=positions_q, kv_positions=kv_positions)
    args = (q, k_new, v_new, pk, pv, jnp.int32(LAYER), tables, lengths,
            active)

    kernel = _attend(*args, impl="pallas", **kw)
    xla = _attend(*args, impl="xla", **kw)

    # the third opinion: the strip gathered by hand
    def strip(p, new):
        g = np.asarray(p, np.float32)[LAYER][np.asarray(tables)]
        g = g.reshape(B, S, kvh, HD)
        g[rows, np.asarray(lengths)] = np.asarray(new, np.float32)
        return jnp.asarray(g, dtype)

    plain = dot_product_attention(
        q[:, None], strip(pk, k_new), strip(pv, v_new), causal=True,
        positions_q=positions_q[:, None], positions_kv=kv_positions,
        impl="xla")[:, 0]

    live = np.asarray(active)
    f32 = lambda a: np.asarray(a, np.float32)[live]  # noqa: E731
    np.testing.assert_array_equal(f32(xla), f32(plain))
    assert np.isfinite(f32(kernel)).all()
    if dtype == "float32":
        np.testing.assert_allclose(f32(kernel), f32(plain), atol=1e-5,
                                   rtol=0)
    else:
        # chip_smoke.py's tolerance for flash against the XLA path
        err = np.abs(f32(kernel) - f32(plain)).max()
        assert err <= 5e-2 * np.abs(f32(plain)).max()


def test_choice_is_logged_and_auto_takes_xla_off_the_chip(caplog):
    """``auto`` on the CPU is the XLA path, and says so through
    ``ops.attention``'s logger — what ``chip_smoke.py`` collects."""
    pool = _Pool(np.random.default_rng(0), 2, "float32")
    _null_entries(pool)
    pk, pv, pos, tables, lengths, active = pool.arrays()
    B = tables.shape[0]
    q = jnp.ones((B, 4, HD)); kn = jnp.ones((B, 2, HD))
    kvp = pos[tables].reshape(B, S)
    with caplog.at_level("INFO", logger="kubeflow_rm_tpu.ops.attention"):
        pa.paged_decode_attention(
            q, kn, kn, pk, pv, jnp.int32(0), tables, lengths, active,
            positions_q=lengths, kv_positions=kvp)
    kernels = [r.args[0] for r in caplog.records]
    assert kernels == ["paged_decode_xla"]
    with pytest.raises(ValueError, match="impl"):
        pa.paged_decode_attention(
            q, kn, kn, pk, pv, jnp.int32(0), tables, lengths, active,
            positions_q=lengths, kv_positions=kvp, impl="flash")


# -- compiled for the chip, without one ---------------------------------


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_outside_the_cache(lowered):
    """``lowered.compile()`` with the persistent cache off: a compile
    for a described chip cannot be read back from it; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()



@pytest.mark.parametrize("H,kvh", [(32, 8), (16, 16), (32, 2)],
                         ids=["mistral-gqa", "bench1b-mha",
                              "nemotron-gqa16"])
def test_kernel_compiles_for_the_chip_at_real_widths(one_chip, H, kvh):
    """Mosaic takes the kernel at the benchmark's and the smoke's
    widths, and the pool goes in as it lies: no copy of it, no
    temporary beside it."""
    B, hd, layers, nb, bs, maxb = 16, 128, 2, 3074, 16, 128
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def run(q, kn, vn, pk, pv, layer, tables, lengths):
        return pa._paged_decode_kernel(q, kn, vn, pk, pv, layer, tables,
                                       lengths, interpret=False)

    assert pa.kernel_eligible(sds((B, H, hd), bf16),
                              sds((layers, nb, bs, kvh, hd), bf16))
    compiled = _compiled_outside_the_cache(jax.jit(run).lower(
        sds((B, H, hd), bf16), sds((B, kvh, hd), bf16),
        sds((B, kvh, hd), bf16),
        sds((layers, nb, bs, kvh, hd), bf16),
        sds((layers, nb, bs, kvh, hd), bf16), sds((), jnp.int32),
        sds((B, maxb), jnp.int32), sds((B,), jnp.int32)))
    assert "tpu_custom_call" in compiled.as_text()
    one_block = bs * kvh * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * one_block


@pytest.mark.parametrize("N", [32, 1, 64], ids=["decode-32", "bucket-1",
                                                "bucket-64"])
def test_expert_kernel_compiles_for_the_chip_at_published_widths(one_chip,
                                                                 N):
    """Mosaic takes ``ops/expert_ffn.py``'s kernel at the benchmark's
    widths (128 experts of 1024 x 2688 held) for a decode step's rows
    and the ends of the prefill buckets it serves, and the two weight
    arrays go in as they lie: no temporary of a matrix's size."""
    from kubeflow_rm_tpu.ops import expert_ffn

    held, d, f = 128, 1024, 2688
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = _compiled_outside_the_cache(
        jax.jit(expert_ffn.active_experts_ffn).lower(
            sds((N, d), bf16), sds((held, N), jnp.float32),
            sds((held,), jnp.int32), sds((), jnp.int32),
            sds((held, d, f), bf16), sds((held, f, d), bf16)))
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * d * f


def test_hybrid_decode_step_compiles_for_the_chip_at_published_widths(
        one_chip):
    """The decode step of the hybrid family at the benchmark's widths
    and share (one 11-layer period, 128 of 512 experts held, 32 slots
    of 4096): the chip's compiler takes it, the paged kernel is a
    custom call, each expert layer's few-rows sum one more (no loop
    over the active experts, no grouped matmul), and nothing beside
    the arguments is of a weight's size: an expert's matrices are read
    where they lie, picked by the kernel's index maps out of the
    layer's whole array (a slice of a stack over the layers would be
    copied, 705 MB a matrix a layer a step, before a custom call: why
    they are an array a layer), and the recurrent state is updated in
    place."""
    from unittest import mock

    from kubeflow_rm_tpu.ops import expert_ffn

    from kubeflow_rm_tpu.models import NemotronHConfig, init_params, paging

    cfg = NemotronHConfig(experts_held=(0, 128), dtype=jnp.bfloat16,
                          param_dtype=jnp.bfloat16)
    slots, slot_len, bs = 32, 4096, 16
    maxb = slot_len // bs

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    assert abs(sum(a.size for a in jax.tree.leaves(params)) / 4.648e9
               - 1) < 0.001
    cache = shaped(jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, slots, slot_len, 2 + slots * maxb + slots * maxb // 2, bs)))
    on_chip = lambda *a, **k: ("tpu", 1)  # noqa: E731
    with mock.patch.object(pa, "computation_devices", on_chip), \
            mock.patch.object(expert_ffn, "computation_devices",
                              on_chip), kernel_choices() as chosen:
        lowered = paging.paged_decode_step.lower(
            params, cfg, cache,
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip))
    compiled = _compiled_outside_the_cache(lowered)
    text = compiled.as_text()
    # 32 rows: the few-rows kernel, one call an expert layer beside
    # the paged kernel's one; no while is left in the step
    n_e = cfg.pattern.count("E")
    assert sorted(chosen) == ["held_experts"] * n_e + ["paged_decode"]
    assert text.count('custom_call_target="tpu_custom_call"') == n_e + 1
    assert "ragged-dot" not in text and " while(" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64e6
    # the cache goes out where it came in: state, tails and pool
    assert memory.alias_size_in_bytes > 0.8e9
