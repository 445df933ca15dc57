"""KV-cached decode vs the training forward: exactness + sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.models import LlamaConfig, forward, init_params
from kubeflow_rm_tpu.models.generate import (
    decode_chunk,
    generate,
    init_cache,
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def test_prefill_matches_forward(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    cache = init_cache(cfg, 2, 24)
    logits, cache = decode_chunk(params, cfg, cache, tokens)
    ref = forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-4)
    assert int(cache.offset) == 16


def test_tokenwise_decode_matches_forward(model):
    """Feeding the prompt one token at a time through the cache must
    reproduce the full-sequence forward logits at every position — the
    property that makes the cache an optimization, not a model."""
    cfg, params = model
    T = 12
    tokens = jax.random.randint(jax.random.key(2), (1, T), 0,
                                cfg.vocab_size)
    ref = forward(params, tokens, cfg)

    cache = init_cache(cfg, 1, T)
    outs = []
    for t in range(T):
        logits, cache = decode_chunk(params, cfg, cache,
                                     tokens[:, t:t + 1])
        outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4)


def test_prefill_then_decode_matches_forward(model):
    """The mixed pattern generate() uses: wide prefill + 1-token steps."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.key(3), (2, 10), 0,
                                cfg.vocab_size)
    ref = forward(params, tokens, cfg)
    cache = init_cache(cfg, 2, 10)
    l_pre, cache = decode_chunk(params, cfg, cache, tokens[:, :7])
    l8, cache = decode_chunk(params, cfg, cache, tokens[:, 7:8])
    l9, cache = decode_chunk(params, cfg, cache, tokens[:, 8:9])
    l10, cache = decode_chunk(params, cfg, cache, tokens[:, 9:10])
    got = jnp.concatenate([l_pre, l8, l9, l10], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4)


def test_greedy_generate_is_deterministic_and_extends(model):
    cfg, params = model
    prompt = jax.random.randint(jax.random.key(4), (2, 5), 0,
                                cfg.vocab_size)
    a = generate(params, cfg, prompt, max_new_tokens=6)
    b = generate(params, cfg, prompt, max_new_tokens=6)
    assert a.shape == (2, 11)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a[:, :5]),
                                  np.asarray(prompt))


def test_greedy_matches_forward_argmax(model):
    """The first generated token must equal argmax of the training
    forward's last-position logits."""
    cfg, params = model
    prompt = jax.random.randint(jax.random.key(5), (3, 8), 0,
                                cfg.vocab_size)
    out = generate(params, cfg, prompt, max_new_tokens=1)
    ref = jnp.argmax(forward(params, prompt, cfg)[:, -1, :], axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, -1]),
                                  np.asarray(ref))


def test_sampling_respects_top_k_and_eos(model):
    cfg, params = model
    prompt = jnp.ones((2, 4), jnp.int32)
    out = generate(params, cfg, prompt, max_new_tokens=8,
                   key=jax.random.key(0), temperature=1.0, top_k=5)
    assert out.shape == (2, 12)
    # eos latching: once a row hits eos it must repeat eos
    logits = forward(params, prompt, cfg)
    eos = int(jnp.argmax(logits[0, -1]))  # greedy first token as "eos"
    out = generate(params, cfg, prompt, max_new_tokens=4, eos_id=eos)
    row = np.asarray(out[0, 4:])
    assert row[0] == eos and (row == eos).all()


def test_sharded_decode_matches_single_device(model, devices8):
    """Serving on a mesh: fsdp×tp-sharded decode (donated cache,
    vocab-sharded logits) must reproduce the unsharded logits."""
    from kubeflow_rm_tpu.models.generate import make_decode_step
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh

    cfg, params = model
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices8)
    step = make_decode_step(params, cfg, mesh)

    tokens = jax.random.randint(jax.random.key(7), (4, 9), 0,
                                cfg.vocab_size)
    ref, _ = decode_chunk(params, cfg, init_cache(cfg, 4, 12), tokens)

    cache = init_cache(cfg, 4, 12)
    logits, cache = step(params, cache, tokens)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=1e-4)
    # and a 1-token continuation against the full-forward reference
    nxt = jax.random.randint(jax.random.key(8), (4, 1), 0,
                             cfg.vocab_size)
    l2, cache = step(params, cache, nxt)
    full = forward(params, jnp.concatenate([tokens, nxt], axis=1), cfg)
    np.testing.assert_allclose(np.asarray(l2[:, -1]),
                               np.asarray(full[:, -1]), atol=1e-4)


def test_moe_decode_matches_forward():
    """The cache path carries the Mixtral family: tokenwise decode must
    reproduce the MoE forward logits (capacity high enough that routing
    drops nothing — the regime where decode and forward agree)."""
    from dataclasses import replace

    from kubeflow_rm_tpu.models.mixtral import MixtralConfig
    from kubeflow_rm_tpu.models.mixtral import forward as moe_forward
    from kubeflow_rm_tpu.models import init_params as init_any

    cfg = MixtralConfig.tiny_moe()
    cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    params = init_any(cfg, jax.random.key(0))
    T = 10
    tokens = jax.random.randint(jax.random.key(6), (2, T), 0,
                                cfg.vocab_size)
    ref, _aux = moe_forward(params, tokens, cfg)

    cache = init_cache(cfg, 2, T)
    outs = []
    for t in range(T):
        logits, cache = decode_chunk(params, cfg, cache,
                                     tokens[:, t:t + 1])
        outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4)


def test_fused_greedy_matches_loop_generate(model):
    """The single-program scan decode must be bit-identical to the
    per-token loop under greedy decoding (same argmax chain)."""
    from kubeflow_rm_tpu.models.generate import generate_fused

    cfg, params = model
    prompt = jax.random.randint(jax.random.key(9), (2, 6), 0,
                                cfg.vocab_size)
    loop = generate(params, cfg, prompt, max_new_tokens=7)
    fused = generate_fused(params, cfg, prompt, max_new_tokens=7)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(loop))


def test_fused_eos_latch_and_sampling_shape(model):
    from kubeflow_rm_tpu.models.generate import generate_fused

    cfg, params = model
    prompt = jnp.ones((2, 4), jnp.int32)
    out = generate_fused(params, cfg, prompt, max_new_tokens=8,
                         key=jax.random.key(1), temperature=1.0, top_k=5)
    assert out.shape == (2, 12)
    logits = forward(params, prompt, cfg)
    eos = int(jnp.argmax(logits[0, -1]))
    out = generate_fused(params, cfg, prompt, max_new_tokens=4,
                         eos_id=eos)
    row = np.asarray(out[0, 4:])
    assert row[0] == eos and (row == eos).all()
    with pytest.raises(ValueError, match="PRNG key"):
        generate_fused(params, cfg, prompt, max_new_tokens=1,
                       temperature=0.5)


def test_fused_moe_greedy_matches_loop():
    """Family dispatch inside the fused scan: Mixtral decodes too."""
    from dataclasses import replace

    from kubeflow_rm_tpu.models import init_params as init_any
    from kubeflow_rm_tpu.models.generate import generate_fused
    from kubeflow_rm_tpu.models.mixtral import MixtralConfig

    cfg = MixtralConfig.tiny_moe()
    cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    params = init_any(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(10), (1, 5), 0,
                                cfg.vocab_size)
    loop = generate(params, cfg, prompt, max_new_tokens=5)
    fused = generate_fused(params, cfg, prompt, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(loop))


def test_leftpad_ragged_batch_matches_unpadded_rows(model):
    """The serving batcher's correctness contract: prompts of different
    lengths, left-padded into one static-shape batch with pad_counts,
    must generate bit-identically to each prompt run alone."""
    from kubeflow_rm_tpu.models.generate import generate_fused

    cfg, params = model
    k = jax.random.key(12)
    p_short = jax.random.randint(k, (1, 3), 1, cfg.vocab_size)
    p_long = jax.random.randint(jax.random.key(13), (1, 7), 1,
                                cfg.vocab_size)
    T = 8
    batch = jnp.zeros((2, T), jnp.int32)
    batch = batch.at[0, T - 3:].set(p_short[0])
    batch = batch.at[1, T - 7:].set(p_long[0])
    pads = jnp.array([T - 3, T - 7], jnp.int32)

    out = generate_fused(params, cfg, batch, max_new_tokens=6,
                         pad_counts=pads)
    ref_s = generate_fused(params, cfg, p_short, max_new_tokens=6)
    ref_l = generate_fused(params, cfg, p_long, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out[0, T - 3:]),
                                  np.asarray(ref_s[0]))
    np.testing.assert_array_equal(np.asarray(out[1, T - 7:]),
                                  np.asarray(ref_l[0]))


def test_sharded_fused_generate_matches_single_device(model, devices8):
    """make_generate_step on a dp×fsdp×tp mesh: the whole generation is
    one SPMD program (cache never leaves the device) and greedy output
    must equal the single-device fused path."""
    from kubeflow_rm_tpu.models.generate import (
        generate_fused, make_generate_step,
    )
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh

    cfg, params = model
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices8)
    prompt = jax.random.randint(jax.random.key(11), (4, 6), 0,
                                cfg.vocab_size)
    ref = generate_fused(params, cfg, prompt, max_new_tokens=5,
                         max_len=11)
    step = make_generate_step(params, cfg, mesh, max_new_tokens=5,
                              total_len=11)
    got = step(params, prompt)  # greedy needs no key
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(ValueError, match="total_len"):
        step(params, jnp.ones((4, 9), jnp.int32))
    with pytest.raises(ValueError, match="PRNG key"):
        make_generate_step(params, cfg, mesh, max_new_tokens=2,
                           total_len=12, temperature=0.5)(params, prompt)
    # sampling path compiles and keeps shape on the same mesh
    step_s = make_generate_step(params, cfg, mesh, max_new_tokens=3,
                                total_len=9, temperature=0.9, top_k=7)
    out = step_s(params, prompt, jax.random.key(2))
    assert out.shape == (4, 9)


def test_rewind_cache_truncates_logically(model):
    """rewind_cache masks slots via positions: decode, rewind, then a
    different continuation must match a fresh decode of that prefix."""
    from kubeflow_rm_tpu.models.generate import rewind_cache

    cfg, params = model
    toks = jax.random.randint(jax.random.key(30), (1, 8), 0,
                              cfg.vocab_size)
    cache = init_cache(cfg, 1, 12)
    _, cache = decode_chunk(params, cfg, cache, toks)
    cache = rewind_cache(cache, 5)          # drop the last 3
    cont = jax.random.randint(jax.random.key(31), (1, 2), 0,
                              cfg.vocab_size)
    got, _ = decode_chunk(params, cfg, cache, cont)

    fresh = init_cache(cfg, 1, 12)
    _, fresh = decode_chunk(params, cfg, fresh, toks[:, :5])
    ref, _ = decode_chunk(params, cfg, fresh, cont)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5)


def test_fused_speculative_matches_greedy(model):
    """The single-program speculative decoder: exact vs greedy
    generate on repetitive and random prompts (fp32), with fewer
    device programs than tokens when the text cooperates."""
    from kubeflow_rm_tpu.models.generate import (
        generate_speculative_fused,
    )

    cfg, params = model
    rep = jnp.asarray([[7, 11, 13, 17] * 6], jnp.int32)
    stats = {}
    out = generate_speculative_fused(params, cfg, rep,
                                     max_new_tokens=12, stats=stats)
    ref = generate(params, cfg, rep, max_new_tokens=12)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert 1 <= stats["model_calls"] <= 1 + 12

    rnd = jax.random.randint(jax.random.key(21), (1, 10), 0,
                             cfg.vocab_size)
    out = generate_speculative_fused(params, cfg, rnd,
                                     max_new_tokens=9)
    ref = generate(params, cfg, rnd, max_new_tokens=9)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    with pytest.raises(ValueError, match="batch-1"):
        generate_speculative_fused(params, cfg,
                                   jnp.ones((2, 5), jnp.int32),
                                   max_new_tokens=2)
    with pytest.raises(ValueError, match="longer than"):
        generate_speculative_fused(params, cfg,
                                   jnp.ones((1, 2), jnp.int32),
                                   max_new_tokens=2)


def test_fused_speculative_eos_latches(model):
    from kubeflow_rm_tpu.models.generate import (
        generate_speculative_fused,
    )

    cfg, params = model
    prompt = jnp.ones((1, 4), jnp.int32)
    eos = int(jnp.argmax(forward(params, prompt, cfg)[0, -1]))
    out = generate_speculative_fused(params, cfg, prompt,
                                     max_new_tokens=5, eos_id=eos)
    ref = generate(params, cfg, prompt, max_new_tokens=5, eos_id=eos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sampling_requires_key(model):
    cfg, params = model
    with pytest.raises(ValueError, match="PRNG key"):
        generate(params, cfg, jnp.ones((1, 2), jnp.int32),
                 max_new_tokens=1, temperature=0.7)


def test_fused_int4_matches_loop_tokenwise(model):
    """Unpacking once must not change a single token: fused int4
    decode (nibbles unpacked ahead of the scan) vs the per-token loop
    (which dequants packed leaves in place)."""
    from kubeflow_rm_tpu.models.generate import generate_fused
    from kubeflow_rm_tpu.models.quantize import quantize_params

    cfg, params = model
    q4 = quantize_params(params, bits=4)
    prompt = jax.random.randint(jax.random.key(40), (2, 6), 1,
                                cfg.vocab_size)
    loop = generate(q4, cfg, prompt, max_new_tokens=7)
    fused = generate_fused(q4, cfg, prompt, max_new_tokens=7)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(loop))


def test_engine_matches_one_shot_fused(model):
    """Continuous batching's exactness contract: every request decodes
    bit-identically to a solo ``generate_fused`` call with the same
    slot-sized cache — across ragged prompt lengths, different token
    budgets, early-EOS retirement, and mid-flight admission (more
    requests than slots, so slots are recycled)."""
    from kubeflow_rm_tpu.models.generate import (
        ContinuousBatchingEngine, generate_fused,
    )

    cfg, params = model
    slot_len = 32
    eng = ContinuousBatchingEngine(params, cfg, slots=2,
                                   slot_len=slot_len)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (3, 7, 5, 8)]
    budgets = [4, 9, 6, 5]
    # request 2 retires early: its eos is the model's own first greedy
    # continuation token
    eos_tok = int(jnp.argmax(forward(
        params, jnp.asarray([prompts[2]], jnp.int32), cfg)[0, -1]))
    eos_ids = [None, None, eos_tok, None]
    reqs = [eng.submit(p, max_new_tokens=m, eos_id=e)
            for p, m, e in zip(prompts, budgets, eos_ids)]
    done = eng.run()
    assert len(done) == len(reqs) and all(r.done for r in reqs)

    for p, m, e, r in zip(prompts, budgets, eos_ids, reqs):
        ref = generate_fused(params, cfg, jnp.asarray([p], jnp.int32),
                             max_new_tokens=m, max_len=slot_len,
                             eos_id=e)
        exp = np.asarray(ref[0, len(p):]).tolist()
        if e is not None and e in exp:    # fused latches eos; the
            exp = exp[:exp.index(e) + 1]  # engine retires the slot
        assert r.tokens == exp
    assert reqs[2].tokens == [eos_tok]    # early retirement happened

    stats = eng.stats()
    assert stats["finished_total"] == 4
    assert stats["prefills"] == 4
    assert stats["active_slots"] == 0 and stats["queue_depth"] == 0
    assert 0 < stats["batch_occupancy"] <= 1.0


def test_engine_validation_and_sampling(model):
    """Capacity guard (prefill bucket + budget must fit the slot),
    empty prompts, the sampling key requirement — and that a sampled
    request is reproducible from its key."""
    from kubeflow_rm_tpu.models.generate import ContinuousBatchingEngine

    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=1, slot_len=16)
    with pytest.raises(ValueError, match="slot_len"):
        eng.submit(list(range(1, 10)), max_new_tokens=8)  # 16+8 > 16
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new_tokens=2)
    with pytest.raises(ValueError, match="key"):
        eng.submit([1, 2], max_new_tokens=2, temperature=0.7)

    outs = []
    for _ in range(2):
        e = ContinuousBatchingEngine(params, cfg, slots=1, slot_len=16)
        r = e.submit([3, 5, 7], max_new_tokens=6, temperature=0.8,
                     top_k=5, key=jax.random.key(42))
        e.run()
        outs.append(r.tokens)
    assert outs[0] == outs[1] and len(outs[0]) == 6


def test_engine_accepts_paged_true_only(model):
    """``paged`` chooses nothing (the harness still passes it): True
    builds the engine no keyword builds, anything else is refused."""
    from kubeflow_rm_tpu.models.generate import ContinuousBatchingEngine

    cfg, params = model
    for bad in (False, None, 0):
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatchingEngine(params, cfg, slots=1, slot_len=16,
                                     paged=bad)
    plain = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32)
    keyed = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32,
                                     paged=True)
    assert keyed.stats() == plain.stats() and "paged" not in plain.stats()
    assert not hasattr(plain, "paged")
    assert (jax.tree.map(jnp.shape, keyed.cache)
            == jax.tree.map(jnp.shape, plain.cache))
