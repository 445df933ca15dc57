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

import re

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

    def __init__(self, rng, kvh, dtype, hd=HD):
        self.k = rng.normal(size=(L, NB, BS, kvh, hd)).astype(np.float32)
        self.v = rng.normal(size=(L, NB, BS, kvh, hd)).astype(np.float32)
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


@pytest.mark.parametrize("scene", ["block_boundary", "shared_block",
                                   "inactive_beside_active"])
def test_wide_head_kernel_matches_the_block_table_path(scene):
    """A head wider than a lane tile (Qwen3-Next's 256, 8 query heads
    a KV head): the kernel copies a block as it lies in the pool and
    flattens it in VMEM; interpreted, it says what the XLA path says."""
    kvh, H, hd = 2, 16, 256
    rng = np.random.default_rng(7)
    pool = _Pool(rng, kvh, "float32", hd=hd)
    SCENES[scene](pool)
    while len(pool.tables) < SLOTS:
        pool.seat(0, active=False)
    pk, pv, pos, tables, lengths, active = pool.arrays()
    B = tables.shape[0]
    q, k_new, v_new = (jnp.asarray(rng.normal(size=s), jnp.float32)
                       for s in ((B, H, hd), (B, kvh, hd), (B, kvh, hd)))
    positions_q = jnp.where(active, lengths, U)
    kv_positions = pos[tables].reshape(B, S).at[np.arange(B), lengths].set(
        positions_q)
    args = (q, k_new, v_new, pk, pv, jnp.int32(LAYER), tables, lengths,
            active)
    kw = dict(positions_q=positions_q, kv_positions=kv_positions)
    live = np.asarray(active)
    kernel = np.asarray(_attend(*args, impl="pallas", **kw))[live]
    xla = np.asarray(_attend(*args, impl="xla", **kw))[live]
    np.testing.assert_allclose(kernel, xla, atol=1e-5, rtol=0)


#: LFM2's ratios at its block size: (H, KVH, hd, BS); a slot's strip
#: four blocks
DENSE_H, DENSE_KVH, DENSE_HD, DENSE_BS, DENSE_MAXB = 32, 8, 64, 16, 4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lane_dense_kernel_matches_the_xla_path_at_block_16(dtype):
    """LFM2's head 64 with eight kv heads at a block of 16: the pool
    keeps a token's heads as one row of 512 lanes, and the kernel,
    interpreted, says what the XLA path says for a slot with nothing
    cached, one at a block boundary, a full strip, a shared first
    block, scattered ids, an inactive slot and an empty one; blocks
    past every slot's cached tokens, poisoned with NaN, are never
    read."""
    H, kvh, hd, bs, maxb = (DENSE_H, DENSE_KVH, DENSE_HD, DENSE_BS,
                            DENSE_MAXB)
    assert pa.lane_dense(kvh, hd)
    rng = np.random.default_rng(64)
    nb = 32
    pool = rng.normal(size=(2, L, nb, bs, kvh * hd)).astype(np.float32)
    pool[:, :, 0] = 0                                   # NULL
    # (blocks, cached tokens, active) a slot
    slots = [([2], 0, True), ([3, 4], bs, True),
             ([5, 6, 7, 8], maxb * bs - 1, True),
             ([5, 9, 10], 2 * bs + 3, True),            # 5 is shared
             ([30, 12, 26, 14], 3 * bs + 1, True),      # scattered
             ([15, 16], 20, False), ([17], 0, False)]
    B = len(slots)
    tables = np.zeros((B, maxb), np.int32)
    for b, (blocks, _, _) in enumerate(slots):
        tables[b, :len(blocks)] = blocks
    lengths = np.array([n for _, n, _ in slots], np.int32)
    active = np.array([a for _, _, a in slots])
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(B, kvh, hd)), dtype)
                    for _ in range(2))
    col = np.arange(maxb * bs)[None, :]
    kw = dict(positions_q=jnp.asarray(np.where(active, lengths, U)),
              kv_positions=jnp.asarray(np.where(col <= lengths[:, None],
                                                col, U)))

    def attend(p, impl):
        return np.asarray(_attend(
            q, k_new, v_new, jnp.asarray(p[0], dtype),
            jnp.asarray(p[1], dtype), jnp.int32(LAYER),
            jnp.asarray(tables), jnp.asarray(lengths), jnp.asarray(active),
            impl=impl, **kw), np.float32)

    kernel, xla = attend(pool, "pallas"), attend(pool, "xla")
    assert kernel.shape == (B, H, hd)
    assert np.isfinite(kernel[active]).all()
    if dtype == "float32":
        np.testing.assert_allclose(kernel[active], xla[active], atol=1e-5,
                                   rtol=0)
    else:
        err = np.abs(kernel[active] - xla[active]).max()
        assert err <= 5e-2 * np.abs(xla[active]).max()
    live = {int(x) for b, (_, n, a) in enumerate(slots) if a
            for x in tables[b, :-(-n // bs)]}
    dead = sorted(set(range(1, nb)) - live)
    pool[:, :, dead] = np.nan
    np.testing.assert_array_equal(kernel, attend(pool, "pallas"))


@pytest.mark.parametrize("H,kvh,hd,bs,lanes,takes", [
    (32, 8, 64, 16, True, True),       # LFM2: 512 lanes a token
    (4, 2, 64, 16, True, False),       # 128 lanes, but 4 query heads
    (32, 2, 64, 16, True, True),       # 128 lanes a token
    (32, 8, 64, 8, True, False),       # a block of 8 rows: half a tile
    (32, 3, 64, 16, False, False),     # 192 lanes: 64 left over
    (32, 1, 64, 16, False, False),     # 64 lanes: 64 left over
    (32, 8, 128, 16, False, True),     # Mistral
    (16, 2, 256, 16, False, True),     # Qwen3-Next
], ids=["lfm2", "four-heads", "two-kv-heads", "block-8", "lanes-left-192",
        "lanes-left-64", "mistral", "wide-256"])
def test_kernel_eligible_takes_a_lane_dense_pool_and_no_lanes_left_over(
        H, kvh, hd, bs, lanes, takes):
    """The pool is lane-dense where a head narrower than the lanes
    leaves none over across a token's heads, and the kernel takes
    such a pool where its blocks and query heads tile; a head whose
    ``KVH * hd`` leaves lanes over keeps the 5-D pool and the XLA
    path, and no pool of it passed flat is taken either."""
    bf16 = jnp.bfloat16
    assert pa.lane_dense(kvh, hd) == lanes
    token = (kvh * hd,) if lanes else (kvh, hd)
    q = jax.ShapeDtypeStruct((8, H, hd), bf16)
    pool = jax.ShapeDtypeStruct((2, 64, bs, *token), bf16)
    assert pa.kernel_eligible(q, pool) == takes
    if not lanes:
        flat = jax.ShapeDtypeStruct((2, 64, bs, kvh * hd), bf16)
        assert not pa.kernel_eligible(q, flat)


# -- a whole chunk in one copy -------------------------------------------

#: chunks cut small: four blocks of four tokens (the test sets
#: ``CHUNK_TOKENS``), three chunks a strip, every slot's blocks its own
CBS, CCB, CMAXB, CNB = 4, 4, 12, 128


def _run(first, n):
    return list(range(first, first + n))


#: a layout: (blocks, tokens cached, active) a slot, and the whole
#: chunks the live slots walk — the rest go a block at a time
CHUNK_LAYOUTS = {
    "every_chunk_consecutive": ([
        (_run(2, 12), 47, True), (_run(14, 9), 32, True),
        (_run(23, 12), 44, True), (_run(35, 4), 15, True)], 3 + 2 + 2 + 1),
    "break_inside_a_chunk": ([
        (_run(2, 5) + [50] + _run(8, 6), 47, True),
        (_run(20, 2) + [60] + _run(23, 9), 40, True),
        (_run(32, 6), 21, True), (_run(38, 4), 13, True)], 2 + 1 + 1 + 1),
    "run_across_a_chunk_boundary": ([
        ([44, 45] + _run(2, 8) + [47, 48], 47, True),
        (_run(20, 6) + _run(30, 6), 47, True),
        (_run(10, 2) + _run(52, 6), 30, True),
        (_run(60, 4), 15, True)], 1 + 2 + 1 + 1),
    "partial_last_chunk": ([
        (_run(2, 12), 37, True), (_run(14, 12), 20, True),
        (_run(26, 12), 33, True), (_run(38, 6), 22, True)], 2 + 1 + 2 + 1),
    "null_entries_past_the_strip": ([
        (_run(2, 7) + [0] * 5, 25, True), (_run(10, 4) + [0] * 8, 15, True),
        (_run(14, 9) + [0] * 3, 32, True), (_run(24, 1) + [0] * 11, 3, True),
    ], 1 + 1 + 2 + 0),
    "inactive_and_empty_slots": ([
        (_run(2, 12), 47, False), (_run(14, 2), 0, True),
        (_run(16, 12), 47, True), (_run(28, 1), 0, False)], 3),
    "shorter_than_a_chunk": ([
        (_run(2, 2), 5, True), (_run(4, 1), 1, True),
        (_run(5, 3), 11, True), (_run(8, 4), 12, True)], 0),
}

#: (H, KVH, hd): Mistral's GQA, Trinity's, Qwen3-Next's wide head, and
#: LFM2's head 64 and a tiny config's, whose pools are lane-dense
CHUNK_SHAPES = {"mistral-8kv": (32, 8, 128), "trinity-4kv": (32, 4, 128),
                "wide-256": (16, 2, 256), "lfm2-8kv-64": (32, 8, 64),
                "tiny-2kv-64": (4, 2, 64)}

_attend_chunks = jax.jit(pa.paged_decode_attention, static_argnames=("impl",))


def _scattered(x):
    """A block's id in a pool where no two ids a table names are
    consecutive (even ids, in reverse): every chunk a block at a time."""
    return 0 if x == 0 else 2 * (CNB // 2 - x) + 2


@pytest.mark.parametrize("shape", sorted(CHUNK_SHAPES))
@pytest.mark.parametrize("layout", sorted(CHUNK_LAYOUTS))
def test_whole_chunk_copies_read_what_the_block_copies_read(
        monkeypatch, layout, shape):
    """A chunk whose blocks are live and consecutive in the pool goes
    in one copy for K and one for V: the same bytes at the same offsets
    as a copy a block, so the output is bitwise the output over the
    same strips laid out with no two ids consecutive, and the XLA
    path's within float32 rounding. No block past a slot's cached
    tokens is copied, whole chunk or not: poisoned with NaN, those
    blocks leave the output as it was. ``whole_chunks`` counts the
    chunks the kernel takes whole. A head of 64 goes in as the pool
    keeps it, a token's heads one row of lanes (``lane_dense``)."""
    monkeypatch.setattr(pa, "CHUNK_TOKENS", CBS * CCB)
    assert pa.chunk_blocks(CBS, CMAXB) == CCB
    H, kvh, hd = CHUNK_SHAPES[shape]
    slots, want_whole = CHUNK_LAYOUTS[layout]
    rng = np.random.default_rng(sorted(CHUNK_LAYOUTS).index(layout))
    B = len(slots)
    pool = rng.normal(size=(2, L, CNB, CBS, kvh, hd)).astype(np.float32)
    pool[:, :, 0] = 0                                   # NULL
    tables = np.zeros((B, CMAXB), np.int32)
    for b, (blocks, n, _) in enumerate(slots):
        assert -(-(n + 1) // CBS) <= len(blocks)        # its token's block
        tables[b, :len(blocks)] = blocks
    lengths = np.array([n for _, n, _ in slots], np.int32)
    active = np.array([a for _, _, a in slots])

    # the same strips where no table names two consecutive ids
    spread = np.vectorize(_scattered)(tables).astype(np.int32)
    pool_s = rng.normal(size=pool.shape).astype(np.float32)
    used = np.unique(tables)
    pool_s[:, :, np.vectorize(_scattered)(used)] = pool[:, :, used]

    def walked_whole(t):
        return sum(pa.whole_chunks(t[b], CCB)[-(-n // CBS) // CCB]
                   for b, (_, n, a) in enumerate(slots) if a)

    assert walked_whole(tables) == want_whole
    assert walked_whole(spread) == 0

    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(B, kvh, hd)), jnp.float32)
                    for _ in range(2))
    positions_q = np.where(active, lengths, U)
    col = np.arange(CMAXB * CBS)[None, :]
    kv_positions = np.where(col <= lengths[:, None], col, U)

    def stored(x):
        if pa.lane_dense(kvh, hd):
            x = x.reshape(*x.shape[:3], kvh * hd)
        return jnp.asarray(x)

    def attend(p, t, impl):
        return np.asarray(_attend_chunks(
            q, k_new, v_new, stored(p[0]), stored(p[1]),
            jnp.int32(LAYER), jnp.asarray(t), jnp.asarray(lengths),
            jnp.asarray(active), positions_q=jnp.asarray(positions_q),
            kv_positions=jnp.asarray(kv_positions), impl=impl))

    whole = attend(pool, tables, "pallas")
    np.testing.assert_allclose(whole[active],
                               attend(pool, tables, "xla")[active],
                               atol=1e-5, rtol=0)
    # every block a table names past its slot's cached tokens, NULL too
    live = {int(x) for b, (_, n, a) in enumerate(slots) if a
            for x in tables[b, :-(-n // CBS)]}
    dead = np.array(sorted(set(used.tolist()) - live))
    for p, ids in ((pool, dead), (pool_s, np.vectorize(_scattered)(dead))):
        p[:, :, ids] = np.nan
    np.testing.assert_array_equal(whole, attend(pool, tables, "pallas"))
    np.testing.assert_array_equal(whole, attend(pool_s, spread, "pallas"))


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



@pytest.mark.parametrize(
    "H,kvh,B,layers,nb,maxb",
    [(32, 8, 16, 2, 3074, 128), (16, 16, 16, 2, 3074, 128),
     (32, 2, 16, 2, 3074, 128), (32, 4, 32, 4, 20482, 640)],
    ids=["mistral-gqa", "bench1b-mha", "nemotron-gqa16", "trinity-full"])
def test_kernel_compiles_for_the_chip_at_real_widths(one_chip, H, kvh, B,
                                                     layers, nb, maxb):
    """Mosaic takes the kernel at the benchmark's and the smoke's
    widths, whole-chunk copies and block copies both, and the pool
    goes in as it lies: no copy of it, no temporary beside it (at
    Trinity's four full layers a pool of 671 MB a side)."""
    hd, bs = 128, 16
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


def test_lane_dense_kernel_compiles_for_the_chip_at_lfm2_widths(one_chip):
    """Mosaic takes the kernel over LFM2's lane-dense pool at the
    benchmark's widths (64 slots of 2,048, four layers, a block
    ``(16, 512)``), whole-chunk copies and block copies both, and the
    pool goes in as it lies: no copy of it, no temporary beside it."""
    B, H, kvh, hd, bs, layers, nb, maxb = 64, 32, 8, 64, 16, 4, 8194, 128
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def run(q, kn, vn, pk, pv, layer, tables, lengths):
        return pa._paged_decode_kernel(q, kn, vn, pk, pv, layer, tables,
                                       lengths, interpret=False)

    pool = sds((layers, nb, bs, kvh * hd), bf16)
    assert pa.kernel_eligible(sds((B, H, hd), bf16), pool)
    compiled = _compiled_outside_the_cache(jax.jit(run).lower(
        sds((B, H, hd), bf16), sds((B, kvh, hd), bf16),
        sds((B, kvh, hd), bf16), pool, pool, sds((), jnp.int32),
        sds((B, maxb), jnp.int32), sds((B,), jnp.int32)))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.findall(r"= bf16\[4,8194,16,512\]\S* copy\(", text)
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
    place by each Mamba layer's kernel, one more custom call: no
    fusion or copy of the whole state is left in the step."""
    from unittest import mock

    from kubeflow_rm_tpu.ops import expert_ffn, mamba_decode

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
                              on_chip), \
            mock.patch.object(mamba_decode, "computation_devices",
                              on_chip), kernel_choices() as chosen:
        lowered = paging.paged_decode_step.lower(
            params, cfg, cache,
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip))
    compiled = _compiled_outside_the_cache(lowered)
    text = compiled.as_text()
    # 32 rows: the few-rows kernel, one call an expert layer, and the
    # state's kernel one a Mamba layer, beside the paged kernel's one;
    # no while is left in the step
    n_e, n_m = cfg.pattern.count("E"), cfg.pattern.count("M")
    assert sorted(chosen) == (["held_experts"] * n_e + ["mamba_decode"] * n_m
                              + ["paged_decode"])
    assert text.count('custom_call_target="tpu_custom_call"') == n_e + n_m + 1
    assert "ragged-dot" not in text and " while(" not in text
    state = "f32[5,32,128,64,128]"
    assert not [line for line in text.splitlines() if state in line
                and (" fusion(" in line or " copy(" in line)]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64e6
    # the cache goes out where it came in: state, tails and pool
    assert memory.alias_size_in_bytes > 0.8e9


def test_wide_head_kernel_reads_the_pool_where_it_lies(one_chip):
    """At head 256 and 2 KV heads a pool block lies in (2, 128) tiles:
    reshaped to (rows, hd) outside the kernel it would be copied whole
    every step (537 MB at the Qwen3-Next cell's pool). The kernel takes
    it as it lies: no temporary of the pool's size."""
    B, H, hd, kvh, layers, nb, bs, maxb = 32, 16, 256, 2, 1, 16386, 16, 512
    bf16 = jnp.bfloat16

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def run(q, kn, vn, pk, pv, layer, tables, lengths):
        return pa._paged_decode_kernel(q, kn, vn, pk, pv, layer, tables,
                                       lengths, interpret=False)

    compiled = _compiled_outside_the_cache(jax.jit(run).lower(
        sds((B, H, hd), bf16), sds((B, kvh, hd), bf16),
        sds((B, kvh, hd), bf16), sds((layers, nb, bs, kvh, hd), bf16),
        sds((layers, nb, bs, kvh, hd), bf16), sds((), jnp.int32),
        sds((B, maxb), jnp.int32), sds((B,), jnp.int32)))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6


def _weight_writes(text, shapes):
    """The top-level ops of a compiled module (outside fused
    computations and custom calls) that write an array of one of
    ``shapes`` (leading 1s dropped), alone or in a tuple: a ``copy``,
    or a fusion whose output lies in HBM. A fusion into VMEM (``S(1)``)
    of a layer's slice is how the matmul that follows reads its
    operand, not a copy; nor are the async ``copy-start``/``copy-done``
    prefetches."""
    found = []
    for comp in re.split(r"\n(?=\S)", text):
        if "fused" in comp.split("\n", 1)[0]:
            continue
        for line in comp.split("\n")[1:]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) (copy|fusion)\(", line)
            if not m:
                continue
            for dims, layout in re.findall(r"\w+\[([\d,]*)\](\{[^}]*\})?",
                                           m.group(1)):
                dims = tuple(int(d) for d in dims.split(",") if d)
                while dims[:1] == (1,):
                    dims = dims[1:]
                if dims in shapes and (m.group(2) == "copy"
                                       or "S(1)" not in layout):
                    found.append(line.strip()[:160])
                    break
    return found


@pytest.mark.parametrize("program", ["decode", "prefill-16", "prefill-128"])
def test_mistral_programs_read_their_projections_in_place(one_chip,
                                                           program):
    """Mistral's serving programs at published widths (two layers, so
    the layer scan stays a while), compiled for the chip: from the
    engine's tree, ``wq``, ``wk`` and ``wv`` kept (out, in), no layer's
    slice of a projection is copied or re-laid in HBM. From the tree as
    trained, (in, out), XLA re-lays ``wq``'s and ``wk``'s slice every
    layer of every step (``copy bf16[1,4096,4096]``, rope and the
    attention read q and k (out, in)), and ``wv``'s in prefill: the
    check sees a copy where there is one."""
    from unittest import mock

    from kubeflow_rm_tpu.models import LlamaConfig, init_params, paging
    from kubeflow_rm_tpu.models.decode import serving_layout

    cfg = LlamaConfig(vocab_size=32000, dim=4096, n_layers=2, n_heads=32,
                      n_kv_heads=8, hidden_dim=14336, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)
    slots, slot_len, bs = 16, 2048, 16
    maxb = slot_len // bs
    D, hd = cfg.dim, cfg.head_dim
    per_layer = set()
    for out in (cfg.n_heads * hd, cfg.n_kv_heads * hd):
        per_layer |= {(D, out), (out, D), (out // hd, hd, D),
                      (D, out // hd, hd)}

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def shaped(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

    trained = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = shaped(jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, slots, slot_len, 2 + slots * maxb + slots * maxb // 2, bs)))

    def compiled_text(params):
        params = shaped(params)
        on_chip = lambda *a, **k: ("tpu", 1)  # noqa: E731
        with mock.patch.object(pa, "computation_devices", on_chip):
            if program == "decode":
                lowered = paging.paged_decode_step.lower(
                    params, cfg, cache, sds((slots,), jnp.int32),
                    sds((slots,), jnp.bool_))
            else:
                T = int(program.split("-")[1])
                lowered = paging.paged_prefill.lower(
                    params, cfg, cache, sds((maxb,), jnp.int32),
                    sds((), jnp.int32), sds((1, T), jnp.int32),
                    sds((), jnp.int32))
        return _compiled_outside_the_cache(lowered).as_text()

    served = compiled_text(jax.eval_shape(serving_layout, trained))
    assert " while(" in served
    assert _weight_writes(served, per_layer) == []
    assert _weight_writes(compiled_text(trained), per_layer)


def test_qwen3_next_decode_step_compiles_for_the_chip_at_published_widths(
        one_chip):
    """The decode step of the Qwen3-Next cut at the benchmark's widths
    and share (one 4-layer period, 256 of 512 SwiGLU experts held, 32
    slots of 8192): the chip's compiler takes it; each Gated DeltaNet
    layer's delta rule is one custom call, each expert layer's few-rows
    sum one more, the paged kernel one (no while, no grouped matmul);
    the delta-rule state, tails and pool go out where they came in, and
    nothing of a weight's or of the pool's size is a temporary."""
    from unittest import mock

    from kubeflow_rm_tpu.models import paging
    from kubeflow_rm_tpu.models.qwen3_next import (
        Qwen3NextConfig, init_params,
    )
    from kubeflow_rm_tpu.ops import expert_ffn, gated_delta

    cfg = Qwen3NextConfig(vocab_size=75968, experts_held=(0, 256),
                          dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, slot_len, bs = 32, 8192, 16
    maxb = slot_len // bs

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    # 3,677,613,120 parameters: the cut as PERF.md counts it
    assert sum(a.size for a in jax.tree.leaves(params)) == 3677613120
    cache = shaped(jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, slots, slot_len, 2 + slots * maxb + slots * maxb // 2, bs)))
    on_chip = lambda *a, **k: ("tpu", 1)  # noqa: E731
    with mock.patch.object(pa, "computation_devices", on_chip), \
            mock.patch.object(expert_ffn, "computation_devices", on_chip), \
            mock.patch.object(gated_delta, "computation_devices", on_chip), \
            kernel_choices() as chosen:
        lowered = paging.paged_decode_step.lower(
            params, cfg, cache,
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip))
    compiled = _compiled_outside_the_cache(lowered)
    text = compiled.as_text()
    assert sorted(chosen) == (["gated_delta_decode"] * 3
                              + ["held_experts"] * 4 + ["paged_decode"])
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    assert "ragged-dot" not in text and " while(" not in text
    memory = compiled.memory_analysis()
    # W_qkvz is an array a layer: sliced out of a stack over the three
    # linear layers, all three layers' (12288, 2048) were copied every
    # step (104 MB of temporaries)
    assert memory.temp_size_in_bytes < 16e6
    assert _weight_writes(text, {(12288, 2048)}) == []
    assert memory.alias_size_in_bytes > 1.0e9
