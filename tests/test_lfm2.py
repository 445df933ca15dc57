"""The LFM2-MoE family (``models.lfm2``, Liquid AI's LFM2-8B-A1B) at a
small size on the CPU, float32, seeded random weights: gated short
convolution layers whose only slot state is a two-column tail, GQA
attention with q/k norms and RoPE over the block pool, a leading dense
layer, sigmoid-routed experts with a bias on the choice, a head tied
to the embedding, and the engine's handling of a cache that is not
addressable by token block. The plain reference is the benchmark's
(``perf/reference_lfm2.py``), which imports nothing of the program.

Tolerances: everything here is float32 on both sides, so what differs
is the order of the sums (a cache against a full pass, tiles of
experts' rows against a loop over experts). 2e-5 on logits of order 1
is some hundred float32 roundings; a dropped gate, a dropped tail or an
ignored bias moves a logit by 1e-2 and more.
"""

import re
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
from kubeflow_rm_tpu.models import lfm2  # noqa: E402
from kubeflow_rm_tpu.parallel import moe  # noqa: E402
from perf import reference_lfm2 as ref  # noqa: E402

TOL = 2e-5
Config = lfm2.Lfm2MoeConfig


def dims(cfg: Config) -> dict:
    """The reference's sizes for a program config (every expert
    held)."""
    return {"pattern": cfg.pattern, "L": cfg.n_layers, "D": cfg.dim,
            "V": cfg.vocab_size, "H": cfg.n_heads, "KVH": cfg.n_kv_heads,
            "hd": cfg.head_dim, "K": cfg.conv_kernel,
            "theta": cfg.rope_theta, "Fd": cfg.dense_dim,
            "E": cfg.n_routed_experts, "top_k": cfg.top_k,
            "F": cfg.expert_dim, "scale": cfg.route_scale,
            "eps": cfg.norm_eps}


@pytest.fixture(scope="module")
def tiny():
    cfg = Config.tiny()
    return cfg, jax.jit(lambda k: lfm2.init_params(cfg, k))(
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
    dense layers then experts, 12 conv and 4 attention layers at head
    64; a slot's whole recurrent state is a two-column tail."""
    published = Config()
    assert published.n_layers == 24 and published.head_dim == 64
    assert published.layer_types.count("conv") == 18
    cfg = replace(published, layer_types=published.layer_types[:16])
    assert cfg.pattern == "CDCDAECECECEAECECECEAECECECEAECE"
    assert (cfg.pattern.count("C"), cfg.n_attention_layers) == (12, 4)
    assert cfg.recurrent_shapes(64) == (None, (12, 64, 2, 2048))
    with pytest.raises(ValueError, match="layer_types"):
        Config(layer_types=("sliding_attention",))
    with pytest.raises(ValueError, match="experts_held"):
        Config(experts_held=(16, 17))


def test_weights_and_forward_match_the_reference():
    """The reference draws the program's weights from the seed alone
    and its forward is the program's; each planted fault of the
    reference moves the logits far past the tolerance."""
    cfg = Config.tiny()
    params = lfm2.init_params(cfg, jax.random.key(11))
    assert "lm_head" not in params       # the head is the embedding
    tokens = np.random.default_rng(1).integers(1, 256, (2, 21))
    got = lfm2.forward(params, jnp.asarray(tokens), cfg)
    weights = ref.init_weights(dims(cfg), 11, jnp.float32)
    want = ref.forward_logits(weights, tokens, dims(cfg))
    assert float(jnp.abs(got - want).max()) < TOL
    for fault in ref.FAULTS:
        off = ref.forward_logits(weights, tokens, dims(cfg), quant=fault)
        assert float(jnp.abs(off - want).max()) > 100 * TOL, fault


@pytest.mark.parametrize("n", [1, 2, 3, 15])
def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny,
                                                                     n):
    """``paged_prefill`` -> ``paged_install`` -> ``paged_decode_step``,
    teacher-forced, against the reference's full forward at every
    position fed, from prompts of 1, 2, 3 and 15 tokens right-padded to
    a bucket of 16: the tail the prefill hands over is the last two
    real columns of ``Bx`` (zeros before a prompt's first), not the
    bucket's last two, and decode carries it on."""
    cfg, params = tiny
    seq = np.random.default_rng(5 + n).integers(1, 256, 24).tolist()
    want = ref.forward_logits(ref.init_weights(dims(cfg), 3, jnp.float32),
                              np.asarray([seq]), dims(cfg))[0]
    last, cache = seat(params, cfg, fresh_cache(cfg), 1, seq[:n], 16,
                       [2, 3, 4, 5, 6, 7])
    got = [last]
    active = jnp.asarray([False, True, False])
    for t in seq[n:-1]:
        logits, cache = paging.paged_decode_step(
            params, cfg, cache, jnp.asarray([0, t, 0], jnp.int32), active)
        got.append(logits[1])
    worst = float(jnp.abs(jnp.stack(got) - want[n - 1:-1]).max())
    assert worst < TOL


@pytest.mark.parametrize("n", [1, 2, 3, 15])
def test_the_tail_is_the_last_real_columns_of_bx(tiny, n):
    """The installed tail of each conv layer is ``Bx`` at the prompt's
    last two real columns, zeros standing in before its first; the
    other slots' tails are left as they were."""
    cfg, params = tiny
    prompt = np.random.default_rng(n).integers(1, 256, n).tolist()
    cache = fresh_cache(cfg)
    cache = replace(cache, conv=cache.conv + 7.0)     # a slot's old tail
    _, cache = seat(params, cfg, cache, 1, prompt, 16, [2, 3, 4, 5])
    # Bx of each conv layer's input, read off the full forward's trunk
    seen = []

    def spy(cfg_, layer, h, ssm, conv, i, mask):
        b, _c, x = jnp.split(lfm2._project(h, layer["in_proj"], cfg.dtype),
                             3, axis=-1)
        seen.append((b * x)[0])
        return lfm2.short_conv(cfg_, layer, h, ssm, conv, i, mask)

    parts = dict(cfg.hybrid_parts(), C=("blocks_c", "recurrent", spy))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Config, "hybrid_parts", lambda self: parts)
        lfm2.forward(params, jnp.asarray([prompt]), cfg)
    for i, bx in enumerate(seen):
        want = jnp.concatenate([jnp.zeros((2, cfg.dim)), bx])[-2:]
        assert float(jnp.abs(cache.conv[i, 1] - want).max()) < 1e-6
        assert float(jnp.abs(cache.conv[i, 0] - 7.0).max()) == 0.0
    if n == 1:
        assert float(jnp.abs(cache.conv[:, 1, 0]).max()) == 0.0


def test_an_idle_row_keeps_its_tail(tiny):
    """A decode step advances the live rows' tails and leaves an idle
    row's as it was."""
    cfg, params = tiny
    _, cache = seat(params, cfg, fresh_cache(cfg), 0, [5, 6, 7], 16,
                    [2, 3, 4])
    before = np.asarray(cache.conv)
    _, after = paging.paged_decode_step(
        params, cfg, cache, jnp.asarray([9, 0, 0], jnp.int32),
        jnp.asarray([True, False, False]))
    after = np.asarray(after.conv)
    assert np.array_equal(after[:, 1:], before[:, 1:])
    assert np.array_equal(after[:, 0, 0], before[:, 0, 1])
    assert not np.array_equal(after[:, 0, 1], before[:, 0, 1])


def test_two_held_halves_add_up_to_the_uncut_layer(tiny):
    """Experts (0, 16) and (16, 16) of 32, each chip's share alone: the
    two shares add up to the layer with all 32 held, which is the
    reference's layer; the assignments held add up too."""
    cfg = Config.tiny(n_routed_experts=32, experts_held=(0, 32))
    params = lfm2.init_params(cfg, jax.random.key(5))
    layer = layer_of(params, "blocks_e", 1)
    h = jax.random.normal(jax.random.key(4), (2, 9, cfg.dim))
    whole, (n_all, _), _ids = lfm2.sparse_moe(cfg, layer, h)
    total, n_held = 0.0, 0
    for first in (0, 16):
        part = {**layer, **{k: layer[k][first:first + 16]
                            for k in ("moe_gate", "moe_up", "moe_down")}}
        out, (n, _), _ids = lfm2.sparse_moe(
            replace(cfg, experts_held=(first, 16)), part, h)
        total = total + out
        n_held += int(n)
    assert float(jnp.abs(total - whole).max()) < TOL
    assert n_held == int(n_all) == 2 * 9 * cfg.top_k
    w = {k: jnp.asarray(v) for k, v in layer.items()}
    want, _chosen = ref._experts(h, w, dims(cfg), None, None,
                                 jnp.ones((2, 9), bool))
    assert float(jnp.abs(whole - want).max()) < TOL


def test_sigmoid_routing_eps_keyword():
    """``route_sigmoid_topk``'s ``eps``: left out, the weights are the
    chosen scores over their sum plus 1e-20, as before the keyword;
    given, over their sum plus ``eps``. The choice is the same."""
    h = jax.random.normal(jax.random.key(0), (5, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8)) * 0.3
    bias = jax.random.normal(jax.random.key(2), (8,)) * 0.1
    idx, wts = moe.route_sigmoid_topk(h, w, bias, 3, 2.5)
    s = jax.nn.sigmoid(jnp.dot(h, w, precision="highest"))
    chosen = jnp.take_along_axis(s, idx, -1)
    old = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * 2.5
    assert bool(jnp.array_equal(wts, old))
    idx6, wts6 = moe.route_sigmoid_topk(h, w, bias, 3, 2.5, eps=1e-6)
    assert bool(jnp.array_equal(idx6, idx))
    new = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6) * 2.5
    assert float(jnp.abs(wts6 - new).max()) < 1e-7
    assert not bool(jnp.array_equal(wts6, wts))


def test_the_other_hybrid_families_keep_their_caches():
    """Nemotron's and Qwen3-Next's caches keep a float32 state beside
    the tail and three counters; this family's has no state and a
    fourth counter, the pairs attended."""
    from kubeflow_rm_tpu.models.nemotron_h import NemotronHConfig
    from kubeflow_rm_tpu.models.qwen3_next import Qwen3NextConfig

    for cfg in (NemotronHConfig.tiny(), Qwen3NextConfig.tiny()):
        state, tail = cfg.recurrent_shapes(3)
        cache = fresh_cache(cfg)
        assert cache.ssm.shape == state and cache.ssm.dtype == jnp.float32
        assert cache.conv.shape == tail
        assert cache.counters.shape == (2, 3)
        assert cache.routes is None
    cache = fresh_cache(Config.tiny())
    assert cache.ssm is None and cache.counters.shape == (2, 4)
    assert cache.conv.shape == (3, 3, 2, 64)
    # three expert layers of the tiny pattern, 3 slots of 32, top 3
    assert cache.routes.shape == (3, 3, 32, 3)


@pytest.mark.parametrize("n", [1, 5])
def test_the_cache_logs_the_experts_each_column_chose(tiny, n):
    """Install writes the prompt's choices at the strip's first columns
    and each decode step the live row's at its column; the idle rows'
    log is left as it was. Float32 on both sides, so the program's
    choice is the reference's own at every column fed."""
    cfg, params = tiny
    seq = np.random.default_rng(40 + n).integers(1, 256, 12).tolist()
    _x, want = ref.routed_hidden(
        ref.init_weights(dims(cfg), 3, jnp.float32), np.asarray([seq]),
        dims(cfg))
    _last, cache = seat(params, cfg, fresh_cache(cfg), 1, seq[:n], 8,
                        [2, 3, 4, 5])
    active = jnp.asarray([False, True, False])
    for t in seq[n:-1]:
        _logits, cache = paging.paged_decode_step(
            params, cfg, cache, jnp.asarray([0, t, 0], jnp.int32), active)
    log = np.asarray(cache.routes)                   # (Le, slots, S, k)
    fed = len(seq) - 1
    got = np.sort(np.moveaxis(log[:, 1, :fed], 0, 1), -1)
    assert np.array_equal(got, np.sort(want["routes"][0, :fed], -1))
    assert not log[:, [0, 2]].any()


def test_the_reference_runs_the_experts_it_is_given():
    """Given its own choice, a pass is the pass that chose; given the
    choice of a pass that ignored ``expert_bias``, it runs those
    experts and says where and by how much its own choice was
    another."""
    cfg = Config.tiny()
    d = dims(cfg)
    weights = ref.init_weights(d, 11, jnp.float32)
    tokens = np.random.default_rng(2).integers(1, 256, (2, 16))
    x, own = ref.routed_hidden(weights, tokens, d)
    assert not own["flip"].any() and not own["gap"].any()
    again, same = ref.routed_hidden(weights, tokens, d,
                                    routes=own["routes"])
    assert float(jnp.abs(again - x).max()) < TOL
    assert not same["flip"].any()
    _x, off = ref.routed_hidden(weights, tokens, d, quant="bias_ignored")
    moved, told = ref.routed_hidden(weights, tokens, d,
                                    routes=off["routes"])
    assert np.array_equal(told["routes"], off["routes"])
    assert told["flip"].any() and float(jnp.abs(moved - x).max()) > 100 * TOL
    assert (told["gap"][told["flip"]] > 0).all()
    assert not told["gap"][~told["flip"]].any()


def test_the_decode_program_names_the_short_convolution(tiny):
    """The conv parts' work in the decode program sits under the named
    scope ``short_conv``, so the device trace can attribute it."""
    cfg, params = tiny
    text = paging.paged_decode_step.lower(
        params, cfg, fresh_cache(cfg), jnp.zeros((3,), jnp.int32),
        jnp.ones((3,), bool)).as_text(debug_info=True)
    assert text.count("short_conv/") >= 3


# -- the engine ---------------------------------------------------------


def test_engine_serves_what_the_full_forward_picks_and_reseats_cleanly(tiny):
    """Six requests over two slots, prompts of 1 to 13 tokens, so slots
    are reseated while their neighbours decode: each answer is the
    greedy continuation of its own prompt by the full forward, whatever
    tail the slot held before; the counters' pairs are the host's
    arithmetic of the contexts."""
    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, n).tolist() for n in (5, 1, 13, 2, 7, 9)]
    news = (6, 12, 4, 9, 7, 5)
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, news)]
    eng.run()
    fwd = jax.jit(lambda p, t: lfm2.forward(p, t, cfg))
    for prompt, req in zip(prompts, reqs):
        logits = fwd(params, jnp.asarray([prompt + req.tokens]))[0]
        assert req.tokens == jnp.argmax(logits[len(prompt) - 1:-1],
                                        -1).tolist()
    s, c = eng.stats(), eng.device_counters()
    assert s["recurrent_state_bytes"] == eng.cache.conv.nbytes > 0
    assert s["kv_pool_bytes"] == (eng.cache.kv.k.nbytes
                                  + eng.cache.kv.v.nbytes) > 0
    assert c["moe_steps_total"] == s["decode_steps"] + s["prefills"]
    assert "window_positions_total" not in c
    # decode: a fed token at context n reads n + 1 keys
    steps = [len(p) + j for p, m in zip(prompts, news) for j in range(m - 1)]
    assert c["decode_full_positions_total"] == sum(n + 1 for n in steps)
    assert c["full_positions_total"] - c["decode_full_positions_total"] == \
        sum(len(p) * (len(p) + 1) // 2 for p in prompts)


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
    with pytest.raises(ValueError, match="recurrent"):
        eng.submit(prompt, max_new_tokens=4, slo_class="batch",
                   speculative=True)
    with pytest.raises(ValueError, match="recurrent"):
        eng.prefill_chain(prompt)


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
        tokens, _info = fleet.submit_and_wait("t", prompt, max_new_tokens=9,
                                              timeout_s=120.0)
        counters = gateway.device_counters()
    finally:
        fleet.close()
    assert tokens == want.tokens
    assert counters["decode_full_positions_total"] > 0


def test_the_family_is_not_loaded_by_the_package():
    """Only a caller that builds the config imports the family."""
    import subprocess
    code = ("import sys, kubeflow_rm_tpu.models, "
            "kubeflow_rm_tpu.models.generate, kubeflow_rm_tpu.models.paging;"
            "print(any(m.endswith('lfm2') for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, check=True,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin"})
    assert out.stdout.strip() == "False"


# -- a head narrower than the lanes: the lane-dense pool ---------------


def wide64():
    """The tiny config at head 64 with two kv heads: a token's heads
    fill the 128 lanes, so the pool keeps them as one row."""
    cfg = Config.tiny(dim=256, n_heads=4, n_kv_heads=2)
    assert (cfg.head_dim, cfg.n_kv_heads) == (64, 2)
    return cfg, lfm2.init_params(cfg, jax.random.key(5))


@pytest.mark.parametrize("n", [1, 15])
def test_a_head_64_pool_is_lane_dense_and_reads_what_a_5d_pool_reads(
        monkeypatch, n):
    """The pool keeps a token as one row of ``KVH * hd`` = 128 lanes.
    Prefill, install and a run of decode steps through it give the
    logits they give through the 5-D pool the shape rule would
    otherwise build, bit for bit, and the reference's within the
    tolerance; ``gather_slot_strip`` reads the slot's strips back as
    (L, S, KVH, hd), the same as from the 5-D pool."""
    cfg, params = wide64()
    seq = np.random.default_rng(9 + n).integers(1, 256, 22).tolist()
    want = ref.forward_logits(ref.init_weights(dims(cfg), 5, jnp.float32),
                              np.asarray([seq]), dims(cfg))[0]

    def serve():
        cache = fresh_cache(cfg)
        last, cache = seat(params, cfg, cache, 1, seq[:n], 16,
                           [2, 3, 4, 5, 6, 7])
        got = [last]
        active = jnp.asarray([False, True, False])
        for t in seq[n:-1]:
            logits, cache = paging.paged_decode_step(
                params, cfg, cache, jnp.asarray([0, t, 0], jnp.int32),
                active)
            got.append(logits[1])
        return cache, jnp.stack(got), paging.gather_slot_strip(cache.kv, 1)

    cache, lanes, strips = serve()
    assert cache.kv.k.shape == cache.kv.v.shape == (1, 34, 4, 128)
    assert cache.kv.block_shape == (4, 2, 64)
    assert [a.shape for a in strips] == [(1, 32, 2, 64)] * 2 + [(32,)]
    assert float(jnp.abs(lanes - want[n - 1:-1]).max()) < TOL
    monkeypatch.setattr(paging, "lane_dense", lambda *a: False)
    cache, heads, strips5 = serve()
    assert cache.kv.k.shape == (1, 34, 4, 2, 64)
    np.testing.assert_array_equal(np.asarray(lanes), np.asarray(heads))
    for a, b in zip(strips, strips5):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_engine_reads_a_lane_dense_pool_through_the_kernel():
    """Requests over two slots at head 64, the decode step's attention
    forced through the paged kernel (interpreted here): each answer is
    the greedy continuation of its prompt by the full forward, the
    program traced the kernel for the lane-dense pool, and the engine
    counts the chunks the kernel walked."""
    from functools import partial
    from unittest import mock

    from kubeflow_rm_tpu.ops.attention import kernel_choices
    from kubeflow_rm_tpu.ops.paged_attention import paged_decode_attention

    cfg, params = wide64()
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    assert eng.cache.kv.k.ndim == 4
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, n).tolist() for n in (6, 1, 11)]
    news = (5, 8, 4)
    reqs = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, news)]
    with mock.patch.object(paging, "paged_decode_attention",
                           partial(paged_decode_attention, impl="pallas")), \
            kernel_choices() as chosen:
        eng.run()
    assert "paged_decode" in chosen and "paged_decode_xla" not in chosen
    fwd = jax.jit(lambda p, t: lfm2.forward(p, t, cfg))
    for prompt, req in zip(prompts, reqs):
        logits = fwd(params, jnp.asarray([prompt + req.tokens]))[0]
        assert req.tokens == jnp.argmax(logits[len(prompt) - 1:-1],
                                        -1).tolist()
    s = eng.stats()
    assert s["kv_chunks_total"] > 0
    assert 0 <= s["kv_chunks_whole_total"] <= s["kv_chunks_total"]


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
    """The cut's decode step at the benchmark's widths (16 layers, all
    32 experts, 64 slots of 2,048): the chip's compiler takes it; each
    expert layer's few-rows sum is a custom call (no while), the four
    attention layers take the paged kernel at head 64 over the
    lane-dense pool (8 x 64 lanes a token), which is neither relaid
    nor gathered (the XLA path relaid it six times a step and gathered
    every slot's strip), the pool and the tails go out where they came
    in, and no weight is copied."""
    from unittest import mock

    from jax.experimental.compilation_cache import compilation_cache

    from kubeflow_rm_tpu.ops import expert_ffn
    from kubeflow_rm_tpu.ops import paged_attention as pa
    from kubeflow_rm_tpu.ops.attention import kernel_choices
    from test_paged_attention import _weight_writes

    cfg = Config(layer_types=Config().layer_types[:16],
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, slot_len, bs = 64, 2048, 16
    maxb = slot_len // bs

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.key(0))))
    # 5,399,129,024 parameters, 10.80 GB: the cut as PERF.md counts it
    assert sum(a.size for a in jax.tree.leaves(params)) == 5399129024
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
    assert cache.kv.k.shape == (4, 2 + slots * maxb, bs, 8 * 64)
    assert sorted(chosen) == ["held_experts"] * 14 + ["paged_decode"] * 4
    assert text.count('custom_call_target="tpu_custom_call"') == 18
    assert "ragged-dot" not in text and " while(" not in text
    assert _pool_copies(text) == []
    matrices = {tuple(a.shape) for a in jax.tree.leaves(params)
                if a.ndim == 2 and min(a.shape) > 64}
    matrices |= {tuple(a.shape[1:]) for a in jax.tree.leaves(params)
                 if a.ndim == 3 and a.shape[0] == 32}
    assert _weight_writes(text, matrices) == []
    memory = compiled.memory_analysis()
    # the pool (1.07 GB) and the tails go out where they came in
    assert memory.alias_size_in_bytes > 1.08e9
    # no strip of 2,048 positions a slot (the XLA path's 2.7 GB): what
    # is left is the weights' staging
    assert memory.temp_size_in_bytes < 3e8


def _pool_copies(text):
    """The top-level copies, relays and transposes of the cut's whole
    pool, 5-D or lane-dense, in a compiled program's text."""
    return re.findall(r"= bf16\[4,8194,16,(?:8,64|512)\]\S* "
                      r"(?:copy|copy-start|transpose)\(", text)


@pytest.mark.parametrize("program", ["prefill", "install"])
def test_admission_programs_leave_the_lane_dense_pool_where_it_lies(
        one_chip, program):
    """An admission's two programs at the benchmark's widths, compiled
    for the chip: the prefill gathers its prompt's strip from the
    lane-dense pool and the install writes the prompt's blocks into it
    in place; neither relays the whole pool first (a 5-D pool at head
    64, kept with the block index minor, was relaid twice a side in
    each)."""
    from unittest import mock

    from kubeflow_rm_tpu.ops import expert_ffn
    from kubeflow_rm_tpu.ops import paged_attention as pa
    from test_paged_attention import _compiled_outside_the_cache

    cfg = Config(layer_types=Config().layer_types[:16],
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, slot_len, bs, bucket = 64, 2048, 16, 128
    maxb = slot_len // bs

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def scalar(shape=(), dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = shaped(jax.eval_shape(
        lambda: lfm2.init_params(cfg, jax.random.key(0))))
    cache = shaped(jax.eval_shape(lambda: paging.init_paged_cache(
        cfg, slots, slot_len, 2 + slots * maxb, bs)))
    prefill = (params, cfg, cache, scalar((maxb,)), scalar(),
               scalar((1, bucket)), scalar())
    on_chip = lambda *a, **k: ("tpu", 1)  # noqa: E731
    with mock.patch.object(pa, "computation_devices", on_chip), \
            mock.patch.object(expert_ffn, "computation_devices", on_chip):
        if program == "prefill":
            lowered = paging.paged_prefill.lower(*prefill)
        else:
            _, tk, tv, tpos, state = jax.eval_shape(paging.paged_prefill,
                                                    *prefill)
            lowered = paging.paged_install.lower(
                cache, *shaped((tk, tv, tpos)), scalar(), scalar((maxb,)),
                scalar((maxb,)), scalar(), shaped(state))
    text = _compiled_outside_the_cache(lowered).as_text()
    assert "bf16[4,8194,16,512]" in text
    assert _pool_copies(text) == []
