"""The Qwen3-Next family (``models.qwen3_next``) at a small size on the
CPU, float32, seeded random weights: the three kinds of part, the
cache's delta-rule state, and the engine's handling of a family whose
state is not addressable by token block. The plain reference is the
benchmark's (``perf/reference_qwen3_next.py``), which imports nothing
of the program.

Tolerances: everything here is float32 on both sides, so what differs
is the order of the sums (a chunked delta rule against its recurrence,
a loop over experts against tiles of their rows, a cache against a
full pass). 2e-5 on logits of order 1 is some hundred float32
roundings; a lost state, an unmasked pad column or a dropped expert
moves a logit by 1e-2 and more.
"""

import sys
from dataclasses import replace
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from kubeflow_rm_tpu.models import ContinuousBatchingEngine, paging  # noqa: E402,E501
from kubeflow_rm_tpu.models import qwen3_next as qn  # noqa: E402
from kubeflow_rm_tpu.models.qwen3_next import init_params  # noqa: E402
from kubeflow_rm_tpu.ops import gated_delta  # noqa: E402
from kubeflow_rm_tpu.parallel.moe import held_experts_ffn  # noqa: E402
from perf import reference_qwen3_next as ref  # noqa: E402

TOL = 2e-5
Config = qn.Qwen3NextConfig


def dims(cfg: Config) -> dict:
    """The reference's sizes for a program config."""
    return {"pattern": cfg.pattern, "L": cfg.n_layers, "D": cfg.dim,
            "V": cfg.vocab_size, "H": cfg.n_heads, "KVH": cfg.n_kv_heads,
            "hd": cfg.head_dim, "rot": cfg.rotary_dim,
            "theta": cfg.rope_theta, "Hk": cfg.linear_key_heads,
            "Hv": cfg.linear_value_heads, "dk": cfg.linear_key_dim,
            "dv": cfg.linear_value_dim, "K": cfg.conv_kernel,
            "held": cfg.experts_held[1], "router": cfg.n_routed_experts,
            "first": cfg.experts_held[0], "top_k": cfg.top_k,
            "F": cfg.expert_dim, "Fs": cfg.shared_dim, "eps": cfg.norm_eps}


@pytest.fixture(scope="module")
def tiny():
    cfg = Config.tiny()
    return cfg, jax.jit(lambda k: init_params(cfg, k))(jax.random.key(3))


def layer_of(params, stack, i):
    return {k: v[i] for k, v in params[stack].items()}


def fresh_cache(cfg, slots=3, slot_len=32, bs=4):
    maxb = slot_len // bs
    return paging.init_paged_cache(cfg, slots, slot_len,
                                   2 + slots * maxb + maxb, bs)


def seat(params, cfg, cache, slot, prompt, bucket, blocks):
    """Prefill ``prompt`` right-padded to ``bucket`` and install it in
    ``slot`` over ``blocks``; returns (last logits, cache, state)."""
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
    return last, cache, state


def test_the_layers_alternate_as_published():
    cfg = Config(n_layers=8)
    assert cfg.pattern == "LELELEFE" * 2
    assert (cfg.n_attention_layers, cfg.rotary_dim, cfg.conv_dim) == (
        2, 64, 8192)
    assert cfg.recurrent_shapes(32) == ((6, 32, 32, 128, 128),
                                        (6, 32, 3, 8192))
    with pytest.raises(ValueError, match="full attention"):
        Config(n_layers=3)


# -- the program against the plain reference ----------------------------


@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["whole", "share"])
def test_weights_and_forward_match_the_reference(held):
    """Leaf for leaf the reference draws what the program draws, and the
    full forward agrees on logits: every kind of part."""
    cfg = Config.tiny(experts_held=held)
    d = dims(cfg)
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(7))
    w = ref.init_weights(d, 7, jnp.float32)
    stacks = {"L": "blocks_l", "E": "blocks_e", "F": "blocks_f"}
    for at, kind in enumerate(cfg.pattern):
        i = cfg.pattern[:at].count(kind)
        for name, leaf in ref.layer_weights(w, d, at).items():
            mine = params[stacks[kind]][name][i]
            assert leaf.dtype == mine.dtype and bool(jnp.all(leaf == mine)), \
                (kind, name)
    for name in ("lm_head", "norm_f"):
        assert bool(jnp.all(ref.top_weights(w, d, name) == params[name]))
    tokens = np.random.default_rng(1).integers(1, 256, (2, 37))
    mine = jax.jit(lambda p, t: qn.forward(p, t, cfg))(params,
                                                      jnp.asarray(tokens))
    want = ref.forward_logits(w, tokens, d)
    assert float(jnp.abs(mine - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 0.3          # logits of order 1


def test_w_qkvz_is_an_array_a_layer_drawn_as_the_stack_was(tiny):
    """``W_qkvz`` is a list with an array a layer (sliced out of one
    stack over the linear layers, every layer's was copied every decode
    step), and each is the layer the stacked draw gave: the leaf's key
    in the tree's order, split a layer, under ``vmap``. At the cell's
    widths the count stays 3,677,613,120."""
    cfg, params = tiny
    shapes = qn.param_spec_shapes(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, (tuple, list)))
    names = [tuple(p.key for p in path) for path, _ in flat]
    key = jax.random.split(jax.random.key(3), len(flat))[
        names.index(("blocks_l", "w_qkvz"))]
    Ll = cfg.pattern.count("L")
    one = shapes["blocks_l"]["w_qkvz"][0]
    stacked = jax.jit(jax.vmap(
        lambda k: qn._init_leaf(cfg, "w_qkvz", one, k)))(
        jax.random.split(key, Ll))
    mine = params["blocks_l"]["w_qkvz"]
    assert isinstance(mine, list) and len(mine) == Ll
    for i in range(Ll):
        assert mine[i].shape == one
        np.testing.assert_array_equal(mine[i], stacked[i])
    cut = Config(vocab_size=75968, experts_held=(0, 256),
                 dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    tree = jax.eval_shape(lambda: init_params(cut, jax.random.key(0)))
    assert [a.shape for a in tree["blocks_l"]["w_qkvz"]] == [(12288,
                                                             2048)] * 3
    assert sum(a.size for a in jax.tree.leaves(tree)) == 3677613120


def test_reference_head_readings_are_its_logits():
    """The reference's blocked head says of each column what its whole
    logits say: the served token's gap, the argmax, the largest size."""
    cfg = Config.tiny()
    d = dims(cfg)
    w = ref.init_weights(d, 7, jnp.float32)
    tokens = np.random.default_rng(2).integers(1, 256, (2, 9))
    logits = ref.forward_logits(w, tokens, d)
    picked = np.where(np.arange(9)[None] > 3, tokens, -1)
    with mock.patch.object(ref, "HEAD_ROWS", 5):
        gaps, top, big = ref.head_readings(
            w, ref.final_hidden(w, tokens, d), d, picked)
    at = jnp.take_along_axis(logits, jnp.maximum(jnp.asarray(picked), 0)[
        ..., None], -1)[..., 0]
    seen = jnp.asarray(picked) >= 0
    want = jnp.where(seen, logits.max(-1) - at, 0.0)
    assert float(jnp.abs(gaps - want).max()) < 1e-6
    assert bool(jnp.all(top == jnp.where(seen, jnp.argmax(logits, -1), -1)))
    assert abs(big - float(jnp.abs(logits)[seen].max())) < 1e-6


def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny):
    """``paged_prefill`` -> ``paged_install`` -> ``paged_decode_step``,
    teacher-forced, against the reference's full forward at every
    position fed: the state a slot carries is the state of the
    recurrence, the pool's strip the attention layer's rotated keys."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    seq = rng.integers(1, 256, 23).tolist()
    n = 9
    want = ref.forward_logits(ref.init_weights(dims(cfg), 3, jnp.float32),
                              np.asarray([seq]), dims(cfg))[0]
    last, cache, _ = seat(params, cfg, fresh_cache(cfg), 1, seq[:n], 16,
                          [2, 3, 4, 5, 6, 7])
    got = [last]
    active = jnp.asarray([False, True, False])
    for t in seq[n:]:
        logits, cache = paging.paged_decode_step(
            params, cfg, cache, jnp.asarray([0, t, 0], jnp.int32), active)
        got.append(logits[1])
    worst = float(jnp.abs(jnp.stack(got) - want[n - 1:]).max())
    assert worst < TOL


@pytest.mark.parametrize("T", [8, 16, 13, 21, 3])
def test_chunked_rule_equals_the_recurrence_in_the_mixer(tiny, T):
    """One Gated DeltaNet mixer over T columns at once (chunks of 8: T
    a multiple, not a multiple, shorter than a chunk) against the same
    columns one at a time, from a state and tail not zero."""
    cfg, params = tiny
    layer = layer_of(params, "blocks_l", 1)
    (Ll, _, Hv, dk, dv), (_, _, km1, cd) = cfg.recurrent_shapes(2)
    h = jax.random.normal(jax.random.key(T), (2, T, cfg.dim))
    s0 = jax.random.normal(jax.random.key(1), (Ll, 2, Hv, dk, dv)) * 0.1
    c0 = jax.random.normal(jax.random.key(2), (Ll, 2, km1, cd))
    y, s, c = qn.gated_delta_mix(cfg, layer, h, s0, c0, 1,
                                 jnp.ones((2, T), bool))
    ys, s1, c1 = [], s0, c0
    for t in range(T):
        yt, s1, c1 = qn.gated_delta_mix(cfg, layer, h[:, t:t + 1], s1, c1,
                                        1, jnp.ones((2, 1), bool))
        ys.append(yt)
    assert float(jnp.abs(y - jnp.concatenate(ys, 1)).max()) < TOL
    assert float(jnp.abs(s - s1).max()) < TOL
    assert bool(jnp.all(c == c1))
    # the other layers' state and tail are not touched
    for i in (0, 2):
        assert bool(jnp.all(s[i] == s0[i])) and bool(jnp.all(c[i] == c0[i]))


def test_right_padded_bucket_leaves_the_unpadded_state_and_logits(tiny):
    cfg, params = tiny
    prompt = np.random.default_rng(2).integers(1, 256, 11).tolist()
    blocks = [2, 3, 4]
    exact, _, (s_a, c_a, _) = seat(params, cfg, fresh_cache(cfg), 0, prompt,
                                   11, blocks)
    padded, _, (s_b, c_b, n_b) = seat(params, cfg, fresh_cache(cfg), 0,
                                      prompt, 16, blocks)
    assert float(jnp.abs(exact - padded).max()) < TOL
    assert float(jnp.abs(s_a - s_b).max()) < TOL
    assert bool(jnp.all(c_a == c_b))
    # pad columns reach no expert: 11 tokens x top_k over four E layers
    assert int(n_b[0]) == 11 * cfg.top_k * 4


def test_inactive_rows_do_not_advance(tiny):
    """A decode step leaves an inactive row's state, tail and counters
    bit for bit, and counts only the live row's expert assignments."""
    cfg, params = tiny
    _, cache, _ = seat(params, cfg, fresh_cache(cfg), 0, [5, 6, 7, 8, 9], 8,
                       [2, 3, 4])
    _, cache, _ = seat(params, cfg, cache, 2, [11, 12, 13], 4, [5, 6])
    before = jax.tree.map(np.asarray, cache)
    _, after = paging.paged_decode_step(
        params, cfg, cache, jnp.asarray([9, 9, 9], jnp.int32),
        jnp.asarray([True, False, False]))
    for row in (1, 2):
        assert np.array_equal(before.ssm[:, row], after.ssm[:, row])
        assert np.array_equal(before.conv[:, row], after.conv[:, row])
        assert int(after.kv.write_idx[row]) == int(before.kv.write_idx[row])
    assert not np.array_equal(before.ssm[:, 0], after.ssm[:, 0])
    assert int(after.kv.write_idx[0]) == int(before.kv.write_idx[0]) + 1
    step = np.asarray(after.counters[0]) - before.counters[0]
    assert step.tolist()[0] == cfg.top_k * 4 and step.tolist()[2] == 1
    assert np.array_equal(after.counters[1], before.counters[1])


def test_decode_through_the_kernel_matches_the_xla_step(tiny):
    """The same decode step with the delta rule's pallas kernel
    (interpreted) in place of the XLA step: every row's logits and
    state agree, and the dead rows' state is left bit for bit."""
    cfg, params = tiny
    _, cache, _ = seat(params, cfg, fresh_cache(cfg), 0, [5, 6, 7, 8, 9], 8,
                       [2, 3, 4])
    _, cache, _ = seat(params, cfg, cache, 2, [11, 12, 13], 4, [5, 6])
    tokens = jnp.asarray([3, 9, 4], jnp.int32)
    active = jnp.asarray([True, False, True])
    before = np.asarray(cache.ssm)
    xla, want = paging.paged_decode_step(       # the cache is donated
        params, cfg, jax.tree.map(jnp.copy, cache), tokens, active)
    with mock.patch.object(gated_delta, "takes_kernel", lambda state: True):
        paging.paged_decode_step.clear_cache()
        got, mine = paging.paged_decode_step(params, cfg, cache, tokens,
                                             active)
    paging.paged_decode_step.clear_cache()
    assert float(jnp.abs(got - xla)[jnp.asarray([0, 2])].max()) < TOL
    assert float(jnp.abs(mine.ssm - want.ssm).max()) < TOL
    assert np.array_equal(np.asarray(mine.ssm)[:, 1], before[:, 1])


# -- the expert layer ---------------------------------------------------


def test_two_shares_and_the_shared_expert_add_up_to_the_uncut_layer(tiny):
    cfg, params = tiny
    layer = layer_of(params, "blocks_e", 0)
    h = jax.random.normal(jax.random.key(4), (2, 9, cfg.dim))
    whole, (n_all, _) = qn.sparse_moe(cfg, layer, h)
    hf = h.reshape(-1, cfg.dim)
    shared = ((jax.nn.silu(hf @ layer["ws_gate"]) * (hf @ layer["ws_up"]))
              @ layer["ws_down"]) * jax.nn.sigmoid(hf @ layer["shared_gate"])
    shared = shared.reshape(h.shape)
    routed, n_held = 0.0, 0
    for first in (0, 8):
        part = {**layer, **{k: layer[k][first:first + 8]
                            for k in ("moe_gate", "moe_up", "moe_down")}}
        out, (n, _) = qn.sparse_moe(replace(cfg, experts_held=(first, 8)),
                                    part, h)
        routed = routed + (out - shared)
        n_held += int(n)
    assert float(jnp.abs(routed + shared - whole).max()) < TOL
    assert n_held == int(n_all) == 2 * 9 * cfg.top_k
    # the reference's layer, given the whole, says the same
    w = {k: jnp.asarray(v) for k, v in layer.items()}
    want = ref._experts(h, w, dims(cfg), None, None, jnp.ones((2, 9), bool))
    assert float(jnp.abs(whole - want).max()) < TOL


@pytest.mark.parametrize("N", [40, 1100], ids=["loop", "grouped"])
def test_no_token_is_dropped_when_all_choose_the_same_experts(N):
    """Every token sent to the same three experts (the router's columns
    decide): a capacity of tokens x top_k / experts would drop most of
    them; here every assignment is computed, by either dispatch, with
    the softmax's renormalised weights."""
    from kubeflow_rm_tpu.parallel.moe import _FEW_ROWS
    assert (N > _FEW_ROWS) == (N == 1100)
    D, F, E, k = 16, 12, 8, 3
    keys = jax.random.split(jax.random.key(0), 4)
    h = jnp.abs(jax.random.normal(keys[0], (N, D))) + 0.1
    gate, up = (jax.random.normal(kk, (E, D, F)) * 0.3 for kk in keys[1:3])
    down = jax.random.normal(keys[3], (E, F, D)) * 0.3
    router = jnp.zeros((D, E)).at[:, jnp.asarray([1, 4, 6])].set(
        jnp.asarray([3.0, 2.0, 1.0]))
    out, (n, active) = held_experts_ffn(
        h, router, None, up, down, 0, k, 1.0, gate=gate, routing="softmax")
    assert int(n) == N * k and int(active) == 3
    p = jax.nn.softmax(h @ router, -1)[:, jnp.asarray([1, 4, 6])]
    p = p / p.sum(-1, keepdims=True)
    want = sum(p[:, [i]] * ((jax.nn.silu(h @ gate[e]) * (h @ up[e]))
                            @ down[e]) for i, e in enumerate((1, 4, 6)))
    assert float(jnp.abs(out - want).max()) < TOL
    live = jnp.arange(N) % 2 == 0
    out2, (n2, _) = held_experts_ffn(
        h, router, None, up, down, 0, k, 1.0, gate=gate, routing="softmax",
        live=live)
    assert int(n2) == N // 2 * k
    assert float(jnp.abs(out2[1::2]).max()) == 0.0
    with pytest.raises(ValueError, match="routing"):
        held_experts_ffn(h, router, None, up, down, 0, k, 1.0,
                         routing="argmax")


# -- the engine ---------------------------------------------------------


def test_engine_serves_what_the_full_forward_picks_and_reseats_cleanly(tiny):
    """Five requests over two slots, so slots are reseated while their
    neighbours decode: each answer is the greedy continuation of its
    own prompt by the full forward, whatever the slot held before."""
    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, n).tolist() for n in (5, 9, 13, 7, 21)]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, (6, 12, 4, 9, 7))]
    eng.run()
    fwd = jax.jit(lambda p, t: qn.forward(p, t, cfg))
    for prompt, req in zip(prompts, reqs):
        logits = fwd(params, jnp.asarray([prompt + req.tokens]))[0]
        want = jnp.argmax(logits[len(prompt) - 1:-1], -1).tolist()
        assert req.tokens == want
    alone = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                     block_size=4)
    again = alone.submit(prompts[4], max_new_tokens=7)
    alone.run()
    assert again.tokens == reqs[4].tokens
    s = eng.stats()
    assert s["recurrent_state_bytes"] == (eng.cache.ssm.nbytes
                                          + eng.cache.conv.nbytes) > 0
    c = eng.device_counters()
    assert c["moe_steps_total"] == s["decode_steps"] + s["prefills"]
    assert c["decode_moe_steps_total"] == s["decode_steps"]
    assert 0 < c["experts_active_total"] <= c["expert_assignments_held_total"]


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
    for call in (lambda: eng.prefill_chain(prompt),
                 lambda: eng.adopt_chain({"covers": [], "keys": []}),
                 lambda: eng.install_chain({}, max_new_tokens=2)):
        with pytest.raises(ValueError, match="recurrent"):
            call()


def test_stats_fetches_nothing_and_a_step_syncs_once(tiny):
    """``gateway.publish`` calls ``stats()`` every step: it must not
    touch the device. The device's counters cost one transfer, on
    demand."""
    cfg, params = tiny
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    eng.submit(list(range(1, 9)), max_new_tokens=6)
    fetches = []
    real = jax.device_get

    def counting(x):
        fetches.append(1)
        return real(x)

    with mock.patch.object(jax, "device_get", counting):
        eng.step()
        assert len(fetches) == eng.stats()["host_syncs_total"] == 1
        for _ in range(5):
            eng.stats()
        assert len(fetches) == 1
        eng.step()
        eng.step()
        assert len(fetches) == eng.stats()["host_syncs_total"] == 3
        eng.device_counters()
        assert len(fetches) == 4


def test_the_family_is_not_loaded_by_the_package():
    """Only a caller that builds the config imports the family: the
    package and the other families' cells load nothing of it."""
    import subprocess
    code = ("import sys, kubeflow_rm_tpu.models, "
            "kubeflow_rm_tpu.models.generate, kubeflow_rm_tpu.models.paging;"
            "print(any(m.endswith(('qwen3_next', 'gated_delta')) "
            "for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, check=True,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin"})
    assert out.stdout.strip() == "False"
