"""Pallas flash attention vs the dense XLA path (fwd + grads), on the
pallas interpreter (CPU conftest). The kernel must be bit-compatible in
semantics with ``dot_product_attention``: causal, GQA, and packed
segments."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.ops.attention import dot_product_attention
from kubeflow_rm_tpu.ops.flash_attention import flash_attention


def make_qkv(key, B=2, T=256, H=4, KVH=2, D=16):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KVH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KVH, D), jnp.float32)
    return q, k, v


def test_flash_matches_dense_causal():
    q, k, v = make_qkv(jax.random.key(0))
    ref = dot_product_attention(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_matches_dense_noncausal():
    q, k, v = make_qkv(jax.random.key(1))
    ref = dot_product_attention(q, k, v, causal=False, impl="xla")
    out = flash_attention(q, k, v, causal=False, block_q=128,
                          block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gradients_match_dense():
    q, k, v = make_qkv(jax.random.key(2), B=1, T=128, H=2, KVH=2, D=8)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=64).sum()

    def loss_ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True,
                                     impl="xla").sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name}")


def test_flash_gqa_gradients():
    q, k, v = make_qkv(jax.random.key(3), B=1, T=128, H=4, KVH=1, D=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=64,
                                block_k=64) ** 2).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=True,
                                      impl="xla") ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name}")


def test_flash_packed_segments_match_dense():
    """Packed documents: local-causal ∧ same-segment in the kernel must
    equal position-causal ∧ same-segment in the dense path."""
    from kubeflow_rm_tpu.training.data import pack_documents

    rng = np.random.default_rng(0)
    docs = [rng.integers(1, 50, size=n).tolist()
            for n in (40, 70, 25, 90, 60)]
    packed = pack_documents(docs, seq_len=128)
    seg = jnp.asarray(packed["segments"][:1])
    pos = jnp.asarray(packed["positions"][:1])

    q, k, v = make_qkv(jax.random.key(4), B=1, T=128, H=2, KVH=2, D=8)
    ref = dot_product_attention(
        q, k, v, causal=True, positions_q=pos, positions_kv=pos,
        segment_ids_q=seg, segment_ids_kv=seg, impl="xla")
    out = flash_attention(q, k, v, causal=True, segment_ids_q=seg,
                          segment_ids_kv=seg, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_impl_flag_validation():
    q, k, v = make_qkv(jax.random.key(5), B=1, T=128, H=2, KVH=2, D=8)
    with pytest.raises(ValueError):
        dot_product_attention(q, k, v, impl="magic")
    # impl="flash" forces the kernel even off-TPU (interpreter)
    out = dot_product_attention(q, k, v, causal=True, impl="flash")
    ref = dot_product_attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_llama_forward_with_flash_matches_xla():
    """End-to-end: the model's attention calls route through the same
    math whether flash or XLA executes them."""
    from kubeflow_rm_tpu.models import LlamaConfig, forward, init_params

    cfg = LlamaConfig.tiny(max_seq_len=128)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 128), 0,
                                cfg.vocab_size)
    ref = forward(params, tokens, cfg)

    import kubeflow_rm_tpu.models.llama as llama_mod
    from kubeflow_rm_tpu.ops import attention as attn_mod
    orig = attn_mod.dot_product_attention

    def forced_flash(*args, **kw):
        kw["impl"] = "flash"
        return orig(*args, **kw)

    llama_mod.dot_product_attention = forced_flash
    try:
        out = forward(params, tokens, cfg)
    finally:
        llama_mod.dot_product_attention = orig
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-4, rtol=3e-4)


def test_auto_eligibility_mirrors_kernel_blocks():
    """Any T that tiles some 128-multiple block stays on the kernel:
    pick_block degrades the preferred block to a divisor of T, so
    lengths like DEFAULT_BLOCK_Q + 128 are eligible AND correct — for
    calls with segment ids, which prefer another tile, as for calls
    without."""
    from kubeflow_rm_tpu.ops.attention import flash_eligible
    from kubeflow_rm_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, PACKED_BLOCK_K, PACKED_BLOCK_Q,
        pick_block, tile_for,
    )

    assert pick_block(1024, 2048) == 1024
    assert pick_block(1024, 1152) == 384   # 1152 = 3 * 384
    assert pick_block(1024, 1280) == 640  # 1280 = 2 * 640
    assert pick_block(256, 16) == 16       # short sequences: block = T

    for seg, prefer_q, prefer_k in (
            (None, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K),
            (jnp.ones((1, 1), jnp.int32), PACKED_BLOCK_Q, PACKED_BLOCK_K)):
        for T in (prefer_q + 128, prefer_k + 128, prefer_k * 2, 8200):
            tile = tile_for(T, seg is not None)
            assert tile == (pick_block(prefer_q, T), pick_block(prefer_k, T))
            q = jnp.zeros((1, T, 2, 8))
            assert flash_eligible(
                q, q, causal=True, positions_q=None, bias=None,
                segment_ids_q=seg) == (T != 8200) == all(tile), (T, seg)
    assert tile_for(2048, True, 128, 256) == (128, 256)   # asked for

    # numeric correctness at a non-power-of-two multiple (T=384 keeps
    # the interpreter fast; preferred 1024 degrades to block 384)
    key = jax.random.key(0)
    B, T, H, D = 1, 384, 2, 8
    qkv = jax.random.normal(key, (3, B, T, H, D), jnp.float32)
    out = flash_attention(qkv[0], qkv[1], qkv[2], causal=True)
    ref = dot_product_attention(qkv[0], qkv[1], qkv[2], causal=True,
                                impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forced_flash_rejects_bias_and_positions():
    q, k, v = make_qkv(jax.random.key(6), B=1, T=128, H=2, KVH=2, D=8)
    pos = jnp.broadcast_to(jnp.arange(128), (1, 128))
    with pytest.raises(ValueError, match="cannot represent"):
        dot_product_attention(q, k, v, impl="flash", positions_q=pos,
                              positions_kv=pos)
    with pytest.raises(ValueError, match="cannot represent"):
        dot_product_attention(q, k, v, impl="flash",
                              bias=jnp.zeros((1, 2, 128, 128)))


def test_pick_block_rejects_unfactorable_lengths():
    """Long T with no 128-multiple divisor must NOT launch a
    full-length score block (VMEM blow-up): explicit calls raise, auto
    falls back to XLA."""
    from kubeflow_rm_tpu.ops.attention import flash_eligible
    from kubeflow_rm_tpu.ops.flash_attention import pick_block

    assert pick_block(1024, 8200) == 0  # 8200 = 8 * 1025, no divisor
    assert pick_block(1024, 100) == 100  # short seqs: block = T
    assert pick_block(1024, 200) == 200  # single block, VMEM-safe
    q = jnp.zeros((1, 8200, 2, 8))
    assert not flash_eligible(q, q, causal=True, positions_q=None,
                              bias=None)
    q_l, k_l, v_l = make_qkv(jax.random.key(7), B=1, T=8200, H=1,
                             KVH=1, D=8)
    with pytest.raises(ValueError, match="block divisor"):
        flash_attention(q_l, k_l, v_l, causal=True)


def test_flash_packed_segments_gradients():
    """The backward kernels' segment machinery (seg index maps, the
    seg branch of the mask) must produce dense-exact gradients."""
    from kubeflow_rm_tpu.training.data import pack_documents

    rng = np.random.default_rng(1)
    docs = [rng.integers(1, 50, size=n).tolist() for n in (40, 70, 25)]
    packed = pack_documents(docs, seq_len=128)
    seg = jnp.asarray(packed["segments"][:1])
    pos = jnp.asarray(packed["positions"][:1])
    q, k, v = make_qkv(jax.random.key(8), B=1, T=128, H=2, KVH=2, D=8)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, segment_ids_q=seg,
                                segment_ids_kv=seg, block_q=64,
                                block_k=64) ** 2).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(
            q, k, v, causal=True, positions_q=pos, positions_kv=pos,
            segment_ids_q=seg, segment_ids_kv=seg, impl="xla") ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name}")


# ---------------------------------------------------------------------
# tile skipping from segment ids
# ---------------------------------------------------------------------

def _segment_rows(case: str, T: int, tile: int) -> np.ndarray:
    """(1, T) segment ids of one packed row, by what the case tries."""
    if case == "many_short":        # a dozen documents a tile
        ids = np.arange(T) // 23 + 1
    elif case == "one_document":    # nothing for the ids to skip
        ids = np.ones(T)
    elif case == "edge_on_tile":    # documents end exactly where tiles do
        ids = np.arange(T) // tile + 1
    elif case == "out_of_order":    # ids fall and come back along the row
        ids = np.array([5, 2, 9, 2, 7, 1])[np.arange(T) * 6 // T]
    elif case == "zero_tail":       # padding's id after the last document
        ids = np.where(np.arange(T) < T - tile - 37,
                       np.arange(T) // 150 + 1, 0)
    return ids.astype(np.int32)[None, :]


@pytest.mark.parametrize("T,tile", [(512, 128), (1024, 256)])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("case", ["many_short", "one_document",
                                  "edge_on_tile", "out_of_order",
                                  "zero_tail"])
def test_segment_tile_skipping(case, group, T, tile, monkeypatch):
    """Forward and the three gradients with the tiles the segment ids
    empty skipped: dense-exact at the file's tolerances, and bit-equal
    to the same kernels made to visit every causal tile."""
    from kubeflow_rm_tpu.ops import flash_attention as fa

    seg = jnp.asarray(_segment_rows(case, T, tile))
    q, k, v = make_qkv(jax.random.key(9), B=1, T=T, H=4, KVH=4 // group,
                       D=8)

    def grads(attend):
        def loss(q, k, v):
            out = attend(q, k, v)
            return (out ** 2).sum(), out
        (_, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
        return (out, *g)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, segment_ids_q=seg,
                               segment_ids_kv=seg, block_q=tile,
                               block_k=tile)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, causal=True,
                                     segment_ids_q=seg,
                                     segment_ids_kv=seg, impl="xla")

    skipping = grads(flash)
    live, causal = fa.flash_tile_counts(seg, block_q=tile, block_k=tile)
    if case in ("many_short", "edge_on_tile", "zero_tail"):
        assert int(live) < int(causal)      # something was skipped
    else:       # one document; ranges of ids out of order all overlap
        assert int(live) == int(causal)
    for a, b, name in zip(skipping, grads(dense), ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5, err_msg=name)

    real = fa.live_tiles
    monkeypatch.setattr(
        fa, "live_tiles", lambda segq, segkv, *tile_and_causal: real(
            jnp.zeros_like(segq), jnp.zeros_like(segkv), *tile_and_causal))
    for a, b, name in zip(skipping, grads(flash), ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _packed_rows(seed: int, rows: int, T: int) -> np.ndarray:
    """Segment ids shaped as the benchmark's ``train_batches`` makes
    them: log-normal documents (median 259, sigma 1, 16 to T tokens)
    laid end to end, cut into full rows, numbered from 1 in each."""
    rng = np.random.default_rng(seed)
    seg = np.empty((rows, T), np.int32)
    left = 0
    for r in range(rows):
        at, doc = 0, 0
        while at < T:
            if left == 0:
                left = int(np.clip(rng.lognormal(np.log(259), 1.0), 16, T))
            take = min(left, T - at)
            doc += 1
            seg[r, at:at + take] = doc
            at, left = at + take, left - take
    return seg


@pytest.mark.parametrize("block_q,block_k", [(512, 512), (512, 256),
                                             (256, 512), (1024, 1024)])
def test_live_tiles_against_brute_force(block_q, block_k):
    """The table the kernels are handed, and the counter made from it,
    against a count over every (query, key) pair: no tile that holds an
    attending pair is dropped, whatever the ids; for ids that rise
    along the row no tile without one is kept."""
    from kubeflow_rm_tpu.ops.flash_attention import (
        flash_tile_counts, live_tiles,
    )

    T = 4096
    rising = _packed_rows(0, 3, T)
    rng = np.random.default_rng(1)
    shuffled = rng.permutation(40)[rising[:1] % 40].astype(np.int32)
    padded = np.where(np.arange(T) < 3000, rising[:1], 0).astype(np.int32)
    pos = np.arange(T)
    nq, nk = T // block_q, T // block_k
    causal_tiles = (np.arange(nk)[None, :] * block_k
                    <= np.arange(nq)[:, None] * block_q + block_q - 1)

    for seg, tight in ((rising, True), (shuffled, False), (padded, False)):
        table = np.asarray(live_tiles(jnp.asarray(seg), jnp.asarray(seg),
                                      block_q, block_k))
        needed = np.stack([
            ((row[:, None] == row[None, :]) & (pos[:, None] >= pos[None, :]))
            .reshape(nq, block_q, nk, block_k).any(axis=(1, 3))
            for row in seg])
        assert not (needed & ~table).any()
        assert not (table & ~causal_tiles).any()
        if tight:
            np.testing.assert_array_equal(table, needed)
        live, causal = flash_tile_counts(jnp.asarray(seg), block_q=block_q,
                                         block_k=block_k)
        assert int(live) == table.sum()
        assert int(causal) == len(seg) * causal_tiles.sum()
        assert needed.sum() <= int(live) <= int(causal)
