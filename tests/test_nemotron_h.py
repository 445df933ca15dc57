"""The hybrid family (``models.nemotron_h``) at a small size on the
CPU, float32, seeded random weights: the three kinds of layer, the
cache's two kinds of state, and the engine's handling of a family whose
state is not addressable by token block. The plain reference is the
benchmark's (``perf/reference_nemotron_h.py``), which imports nothing
of the program.

Tolerances: everything here is float32 on both sides, so what differs
is the order of the sums (a chunked scan against a recurrence, a
grouped matmul against a loop over experts, a cache against a full
pass). 2e-5 on logits of order 1 is some hundred float32 roundings;
a lost state, an unmasked pad column or a dropped expert moves a logit
by 1e-2 and more.
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

from kubeflow_rm_tpu.models import (  # noqa: E402
    ContinuousBatchingEngine, LlamaConfig, NemotronHConfig, init_params,
)
from kubeflow_rm_tpu.models import nemotron_h as nh, paging  # noqa: E402
from kubeflow_rm_tpu.models.decode import OUT_IN, OUT_IN_LEAVES  # noqa: E402
from kubeflow_rm_tpu.ops import mamba_decode  # noqa: E402
from kubeflow_rm_tpu.parallel.moe import held_experts_ffn  # noqa: E402
from perf import reference_nemotron_h as ref  # noqa: E402

TOL = 2e-5


def dims(cfg: NemotronHConfig) -> dict:
    """The reference's sizes for a program config."""
    return {"pattern": cfg.pattern, "D": cfg.dim, "V": cfg.vocab_size,
            "H": cfg.n_heads, "KVH": cfg.n_kv_heads, "hd": cfg.head_dim,
            "Hm": cfg.mamba_heads, "P": cfg.mamba_head_dim,
            "G": cfg.n_groups, "N": cfg.state_size, "K": cfg.conv_kernel,
            "held": cfg.experts_held[1], "router": cfg.n_routed_experts,
            "first": cfg.experts_held[0], "top_k": cfg.top_k,
            "scale": cfg.routed_scaling, "latent": cfg.latent_dim,
            "F": cfg.expert_dim, "Fs": cfg.shared_dim, "eps": cfg.norm_eps}


@pytest.fixture(scope="module")
def tiny():
    cfg = NemotronHConfig.tiny()
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


# -- the program against the plain reference ----------------------------


@pytest.mark.parametrize("held", [(0, 16), (4, 8)], ids=["whole", "share"])
def test_weights_and_forward_match_the_reference(held):
    """Leaf for leaf the reference draws what the program draws, and the
    full forward agrees on logits: every kind of layer of the pattern."""
    cfg = NemotronHConfig.tiny(experts_held=held)
    d = dims(cfg)
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(7))
    w = ref.init_weights(d, 7, jnp.float32)
    stacks = {"M": "blocks_m", "E": "blocks_e", "*": "blocks_a"}
    for at, kind in enumerate(cfg.pattern):
        i = cfg.pattern[:at].count(kind)
        for name, leaf in ref.layer_weights(w, d, at).items():
            mine = params[stacks[kind]][name][i]
            assert leaf.dtype == mine.dtype and bool(jnp.all(leaf == mine)), \
                (kind, name)
    assert bool(jnp.all(ref.top_weights(w, d, "lm_head") == params["lm_head"]))
    tokens = np.random.default_rng(1).integers(1, 256, (2, 37))
    mine = jax.jit(lambda p, t: nh.forward(p, t, cfg))(params,
                                                      jnp.asarray(tokens))
    want = ref.forward_logits(w, tokens, d)
    assert float(jnp.abs(mine - want).max()) < TOL
    assert float(jnp.abs(want).max()) > 0.3          # logits of order 1


def test_prefill_then_decode_through_the_cache_matches_the_reference(tiny):
    """``paged_prefill`` -> ``paged_install`` -> ``paged_decode_step``,
    teacher-forced, against the reference's full forward at every
    position fed: the state a slot carries is the state of the
    recurrence, the pool's strip the attention layer's keys."""
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
def test_chunked_scan_equals_the_recurrence(tiny, T):
    """One Mamba layer over T columns at once (chunks of 8: T a
    multiple, not a multiple, shorter than a chunk) against the same
    columns one at a time, in the second of the stack's two layers."""
    cfg, params = tiny
    layer = layer_of(params, "blocks_m", 1)
    h = jax.random.normal(jax.random.key(T), (2, T, cfg.dim))
    s0 = jax.random.normal(jax.random.key(1), (2, 2, 8, 8, 16)) * 0.1
    c0 = jax.random.normal(jax.random.key(2), (2, 2, 3, cfg.conv_dim))
    y, s, c = nh.mamba_mix(cfg, layer, h, s0, c0, 1, jnp.ones((2, T), bool))
    ys, s1, c1 = [], s0, c0
    for t in range(T):
        yt, s1, c1 = nh.mamba_mix(cfg, layer, h[:, t:t + 1], s1, c1, 1,
                                  jnp.ones((2, 1), bool))
        ys.append(yt)
    assert float(jnp.abs(y - jnp.concatenate(ys, 1)).max()) < TOL
    assert float(jnp.abs(s - s1).max()) < TOL
    assert bool(jnp.all(c == c1))
    # the other layer's state and tail are not touched
    assert bool(jnp.all(s[0] == s0[0])) and bool(jnp.all(c[0] == c0[0]))


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
    # pad columns reach no expert: 11 tokens x top_k over two E layers
    assert int(n_b[0]) == 11 * cfg.top_k * 2


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
    assert step.tolist()[0] == cfg.top_k * 2 and step.tolist()[2] == 1
    assert np.array_equal(after.counters[1], before.counters[1])


def test_decode_through_the_kernel_matches_the_xla_step(tiny):
    """The same decode step with the Mamba-2 pallas kernel (interpreted)
    in place of the XLA step, two of four slots dead: every row's
    logits and state agree, and the dead rows' state and tail are left
    bit for bit."""
    cfg, params = tiny
    _, cache, _ = seat(params, cfg, fresh_cache(cfg, slots=4), 0,
                       [5, 6, 7, 8, 9], 8, [2, 3, 4])
    _, cache, _ = seat(params, cfg, cache, 2, [11, 12, 13], 4, [5, 6])
    tokens = jnp.asarray([3, 9, 4, 7], jnp.int32)
    active = jnp.asarray([True, False, True, False])
    before = jax.tree.map(np.asarray, cache)
    xla, want = paging.paged_decode_step(       # the cache is donated
        params, cfg, jax.tree.map(jnp.copy, cache), tokens, active)
    with mock.patch.object(mamba_decode, "takes_kernel",
                           lambda state, g: True), \
            mock.patch.object(mamba_decode, "_decode_kernel",
                              wraps=mamba_decode._decode_kernel) as kernel:
        paging.paged_decode_step.clear_cache()
        got, mine = paging.paged_decode_step(params, cfg, cache, tokens,
                                             active)
    paging.paged_decode_step.clear_cache()
    assert kernel.call_count == cfg.pattern.count("M")
    assert float(jnp.abs(got - xla).max()) < TOL
    assert float(jnp.abs(mine.ssm - want.ssm).max()) < TOL
    assert bool(jnp.all(mine.conv == want.conv))
    for row in (1, 3):
        assert np.array_equal(np.asarray(mine.ssm)[:, row],
                              before.ssm[:, row])
        assert np.array_equal(np.asarray(mine.conv)[:, row],
                              before.conv[:, row])
    assert not np.array_equal(np.asarray(mine.ssm)[:, 0], before.ssm[:, 0])


# -- the expert layer ---------------------------------------------------


def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer(tiny):
    cfg, params = tiny
    layer = layer_of(params, "blocks_e", 0)
    h = jax.random.normal(jax.random.key(4), (2, 9, cfg.dim))
    whole, (n_all, _) = nh.latent_moe(cfg, layer, h)
    shared = (jnp.square(jax.nn.relu(h @ layer["ws_up"]))
              @ layer["ws_down"])
    routed, n_held = 0.0, 0
    for first in (0, 4, 8, 12):
        part = dict(layer, moe_up=layer["moe_up"][first:first + 4],
                    moe_down=layer["moe_down"][first:first + 4])
        out, (n, _) = nh.latent_moe(replace(cfg, experts_held=(first, 4)),
                                    part, h)
        routed = routed + (out - shared)
        n_held += int(n)
    assert float(jnp.abs(routed + shared - whole).max()) < TOL
    assert n_held == int(n_all) == 2 * 9 * cfg.top_k
    # the reference's layer, given the whole, says the same
    w = {k: jnp.asarray(v) for k, v in layer.items()}
    want = ref._experts(h, w, dims(cfg), None)
    assert float(jnp.abs(whole - want).max()) < TOL


@pytest.mark.parametrize("N", [40, 1100], ids=["loop", "grouped"])
def test_no_token_is_dropped_when_all_choose_the_same_experts(N):
    """Every token sent to the same three experts (the bias decides):
    a capacity of tokens x top_k / experts would drop most of them;
    here every assignment is computed, by either dispatch (few rows:
    the loop over active experts; many: the grouped matmuls)."""
    from kubeflow_rm_tpu.parallel.moe import _FEW_ROWS
    assert (N > _FEW_ROWS) == (N == 1100)
    D, d, f, E, k = 16, 8, 12, 8, 3
    keys = jax.random.split(jax.random.key(0), 4)
    h = jax.random.normal(keys[0], (N, D))
    up = jax.random.normal(keys[1], (E, d, f)) * 0.3
    down = jax.random.normal(keys[2], (E, f, d)) * 0.3
    bias = jnp.zeros((E,)).at[jnp.asarray([1, 4, 6])].set(10.0)
    x = jax.random.normal(keys[3], (N, d))
    out, (n, active) = held_experts_ffn(
        h, jnp.zeros((D, E)), bias, up, down, 0, k, 3.0, expert_in=x)
    assert int(n) == N * k and int(active) == 3
    want = sum(jnp.square(jax.nn.relu(x @ up[e])) @ down[e]
               for e in (1, 4, 6))       # equal scores: weight 3 / 3 each
    assert float(jnp.abs(out - want).max()) < TOL
    # rows marked dead reach no expert and get nothing back
    live = jnp.arange(N) % 2 == 0
    out2, (n2, _) = held_experts_ffn(
        h, jnp.zeros((D, E)), bias, up, down, 0, k, 3.0, expert_in=x,
        live=live)
    assert int(n2) == N // 2 * k
    assert float(jnp.abs(out2[1::2]).max()) == 0.0
    assert float(jnp.abs(out2[::2] - want[::2]).max()) < TOL


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
    fwd = jax.jit(lambda p, t: nh.forward(p, t, cfg))
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
    assert eng.chain_coverage(prompt) == 0
    with pytest.raises(ValueError, match="recurrent"):
        eng.submit(prompt, max_new_tokens=4, slo_class="batch",
                   speculative=True)
    for call in (lambda: eng.prefill_chain(prompt),
                 lambda: eng.adopt_chain({"covers": [], "keys": []}),
                 lambda: eng.install_chain({}, max_new_tokens=2)):
        with pytest.raises(ValueError, match="recurrent"):
            call()


def test_llama_keeps_its_prefix_hits_and_chains():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=64,
                                   block_size=4)
    # no int4 leaf: nothing is copied through a program; the decode
    # projections are turned (out, in), every other leaf is the caller's
    mine = eng.params["blocks"]
    for name, leaf in params["blocks"].items():
        if name in OUT_IN_LEAVES:
            assert name not in mine
            assert bool(jnp.all(mine[name + OUT_IN]
                                == jnp.swapaxes(leaf, 1, 2)))
        else:
            assert mine[name] is leaf
    assert all(eng.params[k] is params[k] for k in params if k != "blocks")
    prompt = list(range(1, 14))
    eng.submit(prompt, max_new_tokens=4)
    eng.run()
    eng.submit(prompt, max_new_tokens=4)
    eng.run()
    s = eng.stats()
    assert s["prefix_hit_tokens"] == 12 and s["prefix_hits_refused_total"] == 0
    assert s["recurrent_state_bytes"] == 0 and eng.device_counters() == {}
    assert eng.chain_coverage(prompt) == 13
    assert eng.prefill_chain(list(range(20, 30)))["covered"] == 10
    spec = eng.submit(prompt, max_new_tokens=4, slo_class="batch",
                      speculative=True)
    eng.run()
    assert spec.done


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


def test_pricer_refuses_the_family_by_name():
    """The admission pricer walks the dense decoder's training step: a
    declaration of this family is refused, not priced as a Llama whose
    extra keys were dropped."""
    from kubeflow_rm_tpu.analysis.jaxcheck import pricer
    with pytest.raises(pricer.DeclarationError, match="nemotron_h"):
        pricer.parse({"family": "nemotron_h", "preset": "tiny"})
    dims = {"vocab_size": 256, "dim": 64, "n_layers": 5, "n_heads": 4,
            "n_kv_heads": 2, "hidden_dim": 128}
    with pytest.raises(pricer.DeclarationError, match="pattern"):
        pricer.parse({"model": {**dims, "pattern": "EMEM*"}})
    assert pricer.parse({"model": dims}).model is not None
