"""The AFMoE family (``models.afmoe``, Arcee's Trinity) at a small size
on the CPU, float32, seeded random weights: window and full attention
layers, the window's ring a slot beside the full layers' block pool,
a leading dense layer, sigmoid-routed experts of which a share is
held, and the engine's handling of a cache that is not addressable by
token block. The plain reference is the benchmark's
(``perf/reference_afmoe.py``), which imports nothing of the program.

Tolerances: everything here is float32 on both sides, so what differs
is the order of the sums (a cache against a full pass, a kernel's
online softmax against a whole one, tiles of experts' rows against a
loop over experts). 2e-5 on logits of order 1 is some hundred float32
roundings; a key one place outside the window, a rotated full layer
or a dropped expert moves a logit by 1e-2 and more.
"""

import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from kubeflow_rm_tpu.models import ContinuousBatchingEngine, paging  # noqa: E402,E501
from kubeflow_rm_tpu.models import afmoe  # noqa: E402
from kubeflow_rm_tpu.ops import paged_attention as pa  # noqa: E402
from kubeflow_rm_tpu.ops.attention import dot_product_attention  # noqa: E402
from kubeflow_rm_tpu.ops.flash_attention import (  # noqa: E402
    _causal_tiles, _window_tiles, flash_attention,
)
from perf import reference_afmoe as ref  # noqa: E402

TOL = 2e-5
Config = afmoe.AfmoeConfig


def dims(cfg: Config) -> dict:
    """The reference's sizes for a program config."""
    return {"pattern": cfg.pattern, "L": cfg.n_layers, "D": cfg.dim,
            "V": cfg.vocab_size, "H": cfg.n_heads, "KVH": cfg.n_kv_heads,
            "hd": cfg.head_dim, "window": cfg.sliding_window,
            "theta": cfg.rope_theta, "Fd": cfg.dense_dim,
            "held": cfg.experts_held[1], "router": cfg.n_routed_experts,
            "first": cfg.experts_held[0], "top_k": cfg.top_k,
            "F": cfg.expert_dim, "Fs": cfg.shared_dim,
            "scale": cfg.route_scale, "eps": cfg.norm_eps}


@pytest.fixture(scope="module")
def tiny():
    cfg = Config.tiny()
    return cfg, jax.jit(lambda k: afmoe.init_params(cfg, k))(
        jax.random.key(3))


def layer_of(params, stack, i):
    return {k: v[i] for k, v in params[stack].items()}


def fresh_cache(cfg, slots=3, slot_len=32, bs=4):
    maxb = slot_len // bs
    return paging.init_paged_cache(cfg, slots, slot_len,
                                   2 + slots * maxb + maxb, bs)


def seat(params, cfg, cache, slot, prompt, bucket, blocks):
    """Prefill ``prompt`` right-padded to ``bucket`` and install it in
    ``slot`` over ``blocks``; returns (last logits, cache)."""
    maxb = cache.kv.block_tables.shape[1]
    padded = jnp.asarray([prompt + [0] * (bucket - len(prompt))], jnp.int32)
    last, tk, tv, tpos, state = paging.paged_prefill(
        params, cfg, cache, jnp.zeros((maxb,), jnp.int32), jnp.int32(0),
        padded, jnp.int32(len(prompt)))
    row = blocks + [paging.NULL_BLOCK] * (maxb - len(blocks))
    dest = blocks + [paging.SINK_BLOCK] * (maxb - len(blocks))
    cache = paging.paged_install(
        cache, tk, tv, tpos, jnp.int32(slot), jnp.asarray(row, jnp.int32),
        jnp.asarray(dest, jnp.int32), jnp.int32(len(prompt)), state)
    return last, cache


def test_the_layers_are_the_published_ones():
    """The cut's sixteen layers: the published ``layer_types[:16]``, two
    dense layers then experts, a full layer every fourth."""
    cfg = Config(experts_held=(0, 16))
    assert cfg.pattern == "WDWDWEFE" + "WEWEWEFE" * 3
    assert (cfg.n_window_layers, cfg.n_attention_layers) == (12, 4)
    assert cfg.embed_scale == 2048 ** 0.5
    with pytest.raises(ValueError, match="full attention"):
        Config(layer_types=("sliding_attention",) * 3)
    with pytest.raises(ValueError, match="layer_types"):
        Config(layer_types=("linear_attention",))


@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["whole", "share"])
def test_weights_and_forward_match_the_reference(held):
    """The reference draws the program's weights from the seed alone
    and its forward is the program's, a share of the experts held or
    every one, over a context longer than the window."""
    cfg = Config.tiny(experts_held=held)
    params = afmoe.init_params(cfg, jax.random.key(11))
    tokens = np.random.default_rng(1).integers(1, 256, (2, 21))
    got = afmoe.forward(params, jnp.asarray(tokens), cfg)
    want = ref.forward_logits(ref.init_weights(dims(cfg), 11, jnp.float32),
                              tokens, dims(cfg))
    assert float(jnp.abs(got - want).max()) < TOL
    for fault in ref.FAULTS:
        off = ref.forward_logits(ref.init_weights(dims(cfg), 11,
                                                  jnp.float32),
                                 tokens, dims(cfg), quant=fault)
        assert float(jnp.abs(off - want).max()) > 100 * TOL


@pytest.mark.parametrize("n", [3, 8, 9, 13])
def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny,
                                                                     n):
    """``paged_prefill`` -> ``paged_install`` -> ``paged_decode_step``,
    teacher-forced, against the reference's full forward at every
    position fed, from prompts shorter than, as long as and longer than
    the window of 8 (at blocks of 4) to a context of 26: the window
    layers' ring is the last window of the prompt, then overwritten in
    place, and each step reads the 8 newest keys, its own among them."""
    cfg, params = tiny
    seq = np.random.default_rng(5 + n).integers(1, 256, 26).tolist()
    want = ref.forward_logits(ref.init_weights(dims(cfg), 3, jnp.float32),
                              np.asarray([seq]), dims(cfg))[0]
    last, cache = seat(params, cfg, fresh_cache(cfg), 1, seq[:n], 16,
                       [2, 3, 4, 5, 6, 7, 8])
    got = [last]
    active = jnp.asarray([False, True, False])
    for t in seq[n:-1]:
        logits, cache = paging.paged_decode_step(
            params, cfg, cache, jnp.asarray([0, t, 0], jnp.int32), active)
        got.append(logits[1])
    worst = float(jnp.abs(jnp.stack(got) - want[n - 1:-1]).max())
    assert worst < TOL


def _ring_scene(W, Wc, n, B=3, H=4, KVH=2, hd=128):
    ks = jax.random.split(jax.random.key(n), 5)
    q = jax.random.normal(ks[0], (B, H, hd))
    kn = jax.random.normal(ks[1], (B, KVH, hd))
    vn = jax.random.normal(ks[2], (B, KVH, hd))
    rk = jax.random.normal(ks[3], (2, B, Wc, KVH, hd))
    rv = jax.random.normal(ks[4], (2, B, Wc, KVH, hd))
    return q, kn, vn, rk, rv


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("n", [4, 7, 8, 12, 35])
def test_a_window_step_reads_the_window_newest_keys_and_no_other(impl, n):
    """The off-by-one, pinned: a token at position ``n`` with a window
    of 8 reads keys ``n - 7 .. n`` (8, itself included). Changing the
    key at ``n - 7`` moves its output, changing the one at ``n - 8``
    (still in the ring of 16) does not; the kernel and the XLA path
    agree."""
    W, Wc = 8, 16
    q, kn, vn, rk, rv = _ring_scene(W, Wc, n)
    lengths = jnp.full((3,), n, jnp.int32)
    active = jnp.asarray([True, False, True])

    def out(rk):
        return pa.window_decode_attention(q, kn, vn, rk, rv, jnp.int32(1),
                                          lengths, active, window=W,
                                          impl=impl)

    base = out(rk)
    other = pa.window_decode_attention(
        q, kn, vn, rk, rv, jnp.int32(1), lengths, active, window=W,
        impl="xla" if impl == "pallas" else "pallas")
    assert float(jnp.abs((base - other)[active]).max()) < 1e-5
    for p, moves in ((n - W + 1, True), (n - W, False)):
        if p < 0:
            continue
        bumped = rk.at[1, :, p % Wc].add(3.0)
        delta = float(jnp.abs((out(bumped) - base)[active]).max())
        assert (delta > 1e-3) == moves, (p, delta)


def test_a_prefill_window_reads_the_window_newest_keys(tiny):
    """The same boundary in the prefill's attention, flash (interpreted
    here) and XLA: query ``p`` reads ``p - 7 .. p``; tiles wholly
    before every query's window are dead, so the table holds fewer
    tiles than the causal test alone."""
    B, T, H, KVH, hd, W = 1, 64, 4, 2, 128, 8
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, KVH, hd))
    v = jax.random.normal(ks[2], (B, T, KVH, hd))
    xla = dot_product_attention(q, k, v, window=W, impl="xla")
    flash = flash_attention(q, k, v, window=W, block_q=16, block_k=8,
                            interpret=True)
    assert float(jnp.abs(flash - xla).max()) < 1e-5
    p = 40
    for key, moves in ((p - W + 1, True), (p - W, False)):
        bumped = dot_product_attention(q, k.at[:, key].add(3.0), v,
                                       window=W, impl="xla")
        assert (float(jnp.abs(bumped - xla)[:, p].max()) > 1e-3) == moves
    live = _window_tiles(4, 8, 16, 8, W)
    assert int(live.sum()) < int(_causal_tiles(4, 8, 16, 8).sum())
    with pytest.raises(ValueError, match="segment"):
        flash_attention(q, k, v, window=W, block_q=16, block_k=8,
                        segment_ids_q=jnp.ones((B, T), jnp.int32),
                        interpret=True)


def test_full_layers_take_no_positions_and_window_layers_rotate(tiny):
    """NoPE: a full layer's q and k are the same whatever positions the
    columns carry (a shift of every position leaves them, and so its
    scores, alone); a window layer's rotate with their positions, its
    scores following the offsets between them alone."""
    cfg, params = tiny
    h = jax.random.normal(jax.random.key(2), (1, 6, cfg.dim))
    pos = jnp.arange(6, dtype=jnp.int32)[None]
    full, win = layer_of(params, "blocks_f", 0), layer_of(params,
                                                          "blocks_w", 0)

    def scores(q, k):
        return jnp.einsum("bqhd,bkhd->bhqk", q[:, :, :cfg.n_kv_heads], k)

    q0, k0, _, _ = afmoe.full_attention(cfg, full, h, pos)
    q1, k1, _, _ = afmoe.full_attention(cfg, full, h, pos + 37)
    assert float(jnp.abs(q1 - q0).max()) == 0.0
    assert float(jnp.abs(k1 - k0).max()) == 0.0
    q0, k0, _, _ = afmoe.window_attention(cfg, win, h, pos)
    q1, k1, _, _ = afmoe.window_attention(cfg, win, h, pos + 37)
    assert float(jnp.abs(q1 - q0).max()) > 1e-2
    assert float(jnp.abs(scores(q1, k1) - scores(q0, k0)).max()) < 1e-4


def test_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer(tiny):
    """A share of the experts a chip (here 2 of 16, as the cut holds 16
    of 128): the eight shares' routed parts, with the shared expert
    counted once, add up to the whole layer, and the reference's layer
    says the same."""
    cfg, params = tiny
    layer = layer_of(params, "blocks_e", 0)
    h = jax.random.normal(jax.random.key(4), (2, 9, cfg.dim))
    whole, (n_all, _) = afmoe.sparse_moe(cfg, layer, h)
    hf = h.reshape(-1, cfg.dim)
    shared = ((jax.nn.silu(hf @ layer["ws_gate"]) * (hf @ layer["ws_up"]))
              @ layer["ws_down"]).reshape(h.shape)
    routed, n_held = 0.0, 0
    for first in range(0, 16, 2):
        part = {**layer, **{k: layer[k][first:first + 2]
                            for k in ("moe_gate", "moe_up", "moe_down")}}
        out, (n, _) = afmoe.sparse_moe(
            replace(cfg, experts_held=(first, 2)), part, h)
        routed = routed + (out - shared)
        n_held += int(n)
    assert float(jnp.abs(routed + shared - whole).max()) < TOL
    assert n_held == int(n_all) == 2 * 9 * cfg.top_k
    w = {k: jnp.asarray(v) for k, v in layer.items()}
    want, (bits, _margin) = ref._experts(h, w, dims(cfg), None, None,
                                         jnp.ones((2, 9), bool))
    assert float(jnp.abs(whole - want).max()) < TOL
    # every expert is held here: a column's bits are its eight choices
    assert {bin(int(b)).count("1") for b in bits.reshape(-1)} == {
        cfg.top_k}


# -- the engine ---------------------------------------------------------


def test_engine_serves_what_the_full_forward_picks_and_reseats_cleanly(tiny):
    """Five requests over two slots, contexts past the window, so slots
    are reseated while their neighbours decode: each answer is the
    greedy continuation of its own prompt by the full forward, whatever
    the slot's ring held before; the counters' pairs are the host's
    arithmetic of the contexts."""
    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, n).tolist() for n in (5, 9, 13, 7, 21)]
    news = (6, 12, 4, 9, 7)
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, news)]
    eng.run()
    fwd = jax.jit(lambda p, t: afmoe.forward(p, t, cfg))
    for prompt, req in zip(prompts, reqs):
        logits = fwd(params, jnp.asarray([prompt + req.tokens]))[0]
        assert req.tokens == jnp.argmax(logits[len(prompt) - 1:-1],
                                        -1).tolist()
    s, c = eng.stats(), eng.device_counters()
    assert s["window_state_bytes"] == (eng.cache.ring_k.nbytes
                                       + eng.cache.ring_v.nbytes) > 0
    assert s["kv_pool_bytes"] == (eng.cache.kv.k.nbytes
                                  + eng.cache.kv.v.nbytes) > 0
    assert s["recurrent_state_bytes"] == 0
    assert c["moe_steps_total"] == s["decode_steps"] + s["prefills"]
    W = cfg.sliding_window
    # decode: a fed token at context n reads n + 1 keys, W at most
    steps = [len(p) + j for p, m in zip(prompts, news) for j in range(m - 1)]
    assert c["decode_full_positions_total"] == sum(n + 1 for n in steps)
    assert c["decode_window_positions_total"] == sum(min(n + 1, W)
                                                     for n in steps)
    pre = [len(p) for p in prompts]
    assert c["full_positions_total"] - c["decode_full_positions_total"] == \
        sum(n * (n + 1) // 2 for n in pre)
    assert (c["window_positions_total"]
            - c["decode_window_positions_total"]) == sum(
        sum(min(i + 1, W) for i in range(n)) for n in pre)


def test_prefix_hit_not_taken_and_block_addressed_paths_refused(tiny):
    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    prompt = list(range(1, 14))
    first = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    second = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    assert second.tokens == first.tokens
    s = eng.stats()
    assert s["prefix_hit_tokens"] == 0 and s["prefix_hits_refused_total"] == 1
    assert eng.chain_coverage(prompt) == 0
    with pytest.raises(ValueError, match="window layers"):
        eng.submit(prompt, max_new_tokens=4, slo_class="batch",
                   speculative=True)
    for call in (lambda: eng.prefill_chain(prompt),
                 lambda: eng.adopt_chain({"covers": [], "keys": []}),
                 lambda: eng.install_chain({}, max_new_tokens=2)):
        with pytest.raises(ValueError, match="ring"):
            call()


def test_served_through_the_fleet(tiny):
    """``ServingFleet`` -> ``ServingGateway`` -> the engine: the answer
    is the engine's own, and the device's counters come through the
    gateway."""
    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import ServingGateway

    cfg, params = tiny
    prompt = np.random.default_rng(9).integers(1, 256, 19).tolist()
    alone = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                     block_size=4)
    want = alone.submit(prompt, max_new_tokens=9)
    alone.run()
    gateway = ServingGateway(ContinuousBatchingEngine(
        params, cfg, slots=2, slot_len=64, block_size=4))
    fleet = ServingFleet({"r0": gateway})
    try:
        tokens, info = fleet.submit_and_wait("t", prompt, max_new_tokens=9,
                                             timeout_s=120.0)
        counters = gateway.device_counters()
    finally:
        fleet.close()
    assert tokens == want.tokens
    assert counters["decode_window_positions_total"] > 0


def test_the_family_is_not_loaded_by_the_package():
    """Only a caller that builds the config imports the family."""
    import subprocess
    code = ("import sys, kubeflow_rm_tpu.models, "
            "kubeflow_rm_tpu.models.generate, kubeflow_rm_tpu.models.paging;"
            "print(any(m.endswith('afmoe') for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, check=True,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin"})
    assert out.stdout.strip() == "False"


# -- what the other cells run did not move ---------------------------------
#
# The window terms are Python-level defaults of None: the other cells'
# calls leave them out. Whether the other cells' programs lower to the
# parent tree's text is a comparison of two trees, made once a change
# (the verify notes say how); what the suite holds is that a call which
# passes None lowers to the text of a call that leaves the term out.


def _without_and_with_none(name):
    """``(lowered leaving the window term out, lowered passing None)``
    for the call ``name``."""
    from kubeflow_rm_tpu.ops.attention import attention_mask

    S = jax.ShapeDtypeStruct
    q, k = S((1, 256, 4, 128), jnp.float32), S((1, 256, 2, 128),
                                              jnp.float32)
    seg = S((1, 256), jnp.int32)
    kw = {"block_q": 64, "block_k": 64, "interpret": True}
    if name == "flash/forward":
        def f(q, k, v, **w):
            return flash_attention(q, k, v, **kw, **w)
        args = (q, k, k)
    elif name == "flash/value_and_grad":
        def f(q, k, v, **w):
            return jax.value_and_grad(lambda *a: flash_attention(
                *a, **kw, **w).sum(), (0, 1, 2))(q, k, v)
        args = (q, k, k)
    elif name == "flash/segmented":
        def f(q, k, v, s, **w):
            return flash_attention(q, k, v, segment_ids_q=s,
                                   segment_ids_kv=s, **kw, **w)
        args = (q, k, k, seg)
    elif name == "attention/xla":
        def f(q, k, v, **w):
            return dot_product_attention(q, k, v, causal=True, impl="xla",
                                         **w)
        args = (q, k, k)
    elif name == "attention/positions":
        pos = S((1, 256), jnp.int32)

        def f(q, k, v, p, **w):
            return dot_product_attention(q, k, v, causal=True, impl="xla",
                                         positions_q=p, positions_kv=p, **w)
        args = (q, k, k, pos)
    elif name == "attention/mask":
        def f(**w):
            return attention_mask(64, 64, causal=True, **w)
        args = ()
    else:                               # the paged kernel's call
        L, NB, BS, KVH, hd, B, H = 2, 10, 4, 2, 128, 3, 4
        args = (S((B, H, hd), jnp.float32), S((B, KVH, hd), jnp.float32),
                S((B, KVH, hd), jnp.float32),
                S((L, NB, BS, KVH, hd), jnp.float32),
                S((L, NB, BS, KVH, hd), jnp.float32), S((), jnp.int32),
                S((B, 2), jnp.int32), S((B,), jnp.int32))

        def f(*a, **w):
            return pa._paged_decode_kernel(*a, interpret=True, **w)
    term = {"paged/kernel": "ring"}.get(name, "window")
    def lowered(**w):
        return jax.jit(lambda *a: f(*a, **w)).lower(*args).as_text()

    return lowered(), lowered(**{term: None})


@pytest.mark.parametrize("name", [
    "flash/forward", "flash/value_and_grad", "flash/segmented",
    "attention/xla", "attention/positions", "attention/mask",
    "paged/kernel"])
def test_a_window_term_of_none_lowers_as_if_left_out(name):
    """The calls the other cells make leave the window terms out; a call
    passing ``window=None`` (``ring=None`` for the paged kernel) lowers
    to the very same text, kernels included."""
    left_out, none = _without_and_with_none(name)
    assert none == left_out


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


def test_decode_step_compiles_for_the_chip_at_published_widths(one_chip):
    """The cut's decode step at the benchmark's widths and share (16
    layers, 16 of 128 experts held, 32 slots of 10,240, a window of
    2,048): the chip's compiler takes it; each full layer's attention
    is the paged kernel, each window layer's the same kernel over its
    ring, each expert layer's few-rows sum one more custom call (no
    while); the pool and the rings go out where they came in, and no
    weight is copied (a projection sliced out of a stack over the
    layers was, every layer's at once)."""
    from unittest import mock

    from jax.experimental.compilation_cache import compilation_cache

    from kubeflow_rm_tpu.ops import expert_ffn
    from kubeflow_rm_tpu.ops.attention import kernel_choices

    cfg = Config(experts_held=(0, 16), dtype=jnp.bfloat16,
                 param_dtype=jnp.bfloat16)
    slots, slot_len, bs = 32, 10240, 16
    maxb = slot_len // bs

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: afmoe.init_params(cfg, jax.random.key(0))))
    # 2,832,867,072 parameters: the cut as PERF.md counts it
    assert sum(a.size for a in jax.tree.leaves(params)) == 2832867072
    cache = shaped(jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, slots, slot_len, 2 + slots * maxb, bs)))
    on_chip = lambda *a, **k: ("tpu", 1)  # noqa: E731
    with mock.patch.object(pa, "computation_devices", on_chip), \
            mock.patch.object(expert_ffn, "computation_devices", on_chip), \
            kernel_choices() as chosen:
        lowered = paging.paged_decode_step.lower(
            params, cfg, cache,
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert sorted(chosen) == (["held_experts"] * 14 + ["paged_decode"] * 4
                              + ["window_decode"] * 12)
    assert text.count('custom_call_target="tpu_custom_call"') == 30
    assert "ragged-dot" not in text and " while(" not in text
    memory = compiled.memory_analysis()
    # the head's float32 logits, (32, 200192), and little else
    assert memory.temp_size_in_bytes < 32e6
    assert memory.alias_size_in_bytes > 4.2e9
