"""The engine's step loop: every slot's last logits live on the device
as one ``(slots, V)`` array, and a token boundary is one pick program,
one blocking device-to-host transfer and one decode program, whatever
the live slots. A sampling request keeps a program of its own (its own
key stream) inside the same single wait."""

import contextlib
import importlib
import types
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.models import LlamaConfig, init_params

# the package exports a function called ``generate``: get the module
generate = importlib.import_module("kubeflow_rm_tpu.models.generate")

SLOTS = 8
SLOT_LEN = 64


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    return cfg, init_params(cfg, jax.random.key(0))


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("slots", SLOTS)
    return generate.ContinuousBatchingEngine(
        params, cfg, slot_len=SLOT_LEN, block_size=8, **kw)


def _prompts(model, sizes, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, model[0].vocab_size, size=n).tolist()
            for n in sizes]


def _seated_prompts(model, seating, sizes, seed):
    """Prompts of ``sizes`` tokens. Under ``prefix-hit`` they share a
    prefix of two whole blocks and half of a third (``block_size`` 8),
    and the first two are that prefix alone: the second admission
    adopts the first one's two whole blocks and forks the third
    copy-on-write, the later ones adopt the two."""
    prompts = _prompts(model, sizes, seed)
    if seating == "prefix-hit":
        shared = _prompts(model, (20,), seed + 1)[0]
        prompts = [shared, shared] + [shared + p for p in prompts[2:]]
    return prompts


def _greedy(model, prompt, budget):
    cfg, params = model
    out = generate.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                            max_new_tokens=budget, max_len=SLOT_LEN)
    return np.asarray(out)[0, len(prompt):].tolist()


@contextlib.contextmanager
def _counted():
    """``_pick_row`` and ``jax.device_get`` wrapped to count calls."""
    with mock.patch.object(generate, "_pick_row",
                           wraps=generate._pick_row) as pick_row, \
            mock.patch.object(jax, "device_get",
                              wraps=jax.device_get) as device_get:
        yield types.SimpleNamespace(pick_row=pick_row,
                                    device_get=device_get)


# ---- (a) one pick program and one sync a step, whatever is live -------

@pytest.mark.parametrize("live", [1, 5, SLOTS])
def test_a_step_is_one_pick_program_and_one_host_sync(model, live):
    eng = _engine(model)
    reqs = [eng.submit(p, max_new_tokens=6)
            for p in _prompts(model, range(3, 3 + live))]
    eng.step()                      # seats them all, first token each
    assert eng.active_slots == live
    before = eng.stats()
    with _counted() as c:
        eng.step()
    after = eng.stats()
    assert c.pick_row.call_count == 1 and c.device_get.call_count == 1
    last, key = c.pick_row.call_args.args
    assert key is None and last.shape == (SLOTS, model[0].vocab_size)
    assert c.pick_row.call_args.kwargs == {"temperature": 0.0,
                                           "top_k": None}
    for name in ("host_syncs_total", "pick_programs_total",
                 "decode_steps"):
        assert after[name] == before[name] + 1, name
    assert eng.occupancy_sum == 2 * live
    assert all(len(r.tokens) == 2 for r in reqs)
    eng.run()
    # the counters follow the boundaries that picked, not the slots:
    # six tokens a request are six boundaries, the last of which only
    # retires
    s = eng.stats()
    assert s["host_syncs_total"] == s["pick_programs_total"] == 6
    assert s["decode_steps"] == 5 and eng.occupancy_sum == 5 * live


def test_an_idle_step_picks_nothing(model):
    eng = _engine(model)
    with _counted() as c:
        assert eng.step() == []
    assert c.pick_row.call_count == 0 and c.device_get.call_count == 0
    assert eng.stats()["host_syncs_total"] == 0


# ---- (b) ragged prompts, admitted and retired mid-flight ---------------

@pytest.mark.parametrize("seating", ["paged", "prefix-hit"])
def test_ragged_requests_match_generate_greedy(model, seating):
    # three slots, eight requests: slots are recycled mid-flight and
    # the live set changes at almost every boundary
    eng = _engine(model, slots=3)
    prompts = _seated_prompts(model, seating,
                              (3, 17, 5, 9, 1, 12, 7, 4), seed=5)
    budgets = [4, 9, 2, 7, 11, 1, 5, 8]
    reqs = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    done = eng.run()
    assert len(done) == len(reqs)
    for p, m, r in zip(prompts, budgets, reqs):
        assert r.tokens == _greedy(model, p, m)
        assert len(r.t_tokens) == m and r.t_tokens == sorted(r.t_tokens)
    s = eng.stats()
    # far fewer picks than tokens: a boundary picks for all its slots
    assert s["host_syncs_total"] == s["pick_programs_total"]
    assert s["decode_steps"] <= s["host_syncs_total"] < sum(budgets)
    if seating == "prefix-hit":
        # the repeat forked its write block; it and a later prompt at
        # the least found the prefix in the pool
        assert s["cow_forks"] >= 1 and s["prefix_hit_tokens"] >= 19 + 16


# ---- (c) a step that mixes greedy and sampling requests ----------------

def _sampled_alone(model, prompt, budget, **kw):
    eng = _engine(model, slots=4)
    r = eng.submit(prompt, max_new_tokens=budget, **kw)
    eng.run()
    return r.tokens


def test_mixed_step_keeps_each_key_stream_and_blocks_once(model):
    prompts = _prompts(model, (6, 4, 9, 5), seed=23)
    sampling = {1: dict(temperature=0.8, top_k=5,
                        key=jax.random.key(42)),
                3: dict(temperature=1.3, top_k=None,
                        key=jax.random.key(7))}
    budget = 7
    eng = _engine(model, slots=4)
    reqs = [eng.submit(p, max_new_tokens=budget, **sampling.get(i, {}))
            for i, p in enumerate(prompts)]
    steps = 0
    with _counted() as c:
        while eng.active_slots or eng.queue_depth:
            gets = c.device_get.call_count
            eng.step()
            steps += 1
            # however many programs, the step waits once
            assert c.device_get.call_count == gets + 1
    # one program for the greedy rows together, one a sampling row
    assert c.pick_row.call_count == steps * 3
    shapes = sorted({call.args[0].shape
                     for call in c.pick_row.call_args_list})
    V = model[0].vocab_size
    assert shapes == [(4, V), (V,)]
    s = eng.stats()
    assert s["host_syncs_total"] == steps == budget
    assert s["pick_programs_total"] == 3 * steps
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        if i in sampling:
            assert r.tokens == _sampled_alone(model, p, budget,
                                              **sampling[i])
        else:
            assert r.tokens == _greedy(model, p, budget)
    # the two streams are not the greedy answer in disguise
    assert any(reqs[i].tokens != _greedy(model, prompts[i], budget)
               for i in sampling)


def test_all_sampling_step_needs_no_greedy_program(model):
    eng = _engine(model, slots=2)
    eng.submit([3, 5, 7], max_new_tokens=3, temperature=0.9,
               key=jax.random.key(1))
    with _counted() as c:
        eng.run()
    V = model[0].vocab_size
    assert {call.args[0].shape for call in c.pick_row.call_args_list} \
        == {(V,)}
    s = eng.stats()
    assert s["pick_programs_total"] == s["host_syncs_total"] == 3


# ---- (d) a dead row is never read ---------------------------------------

@pytest.mark.parametrize("seating", ["paged", "prefix-hit"])
def test_a_reseated_slot_never_shows_the_dead_rows_token(model, seating):
    eng = _engine(model, slots=2)
    first, stays, *later = _seated_prompts(
        model, seating, (5, 8, 6, 7, 4, 9), seed=31)
    a = eng.submit(first, max_new_tokens=2)
    b = eng.submit(stays, max_new_tokens=12)
    while not a.done:
        eng.step()
    # slot 0 is free and b goes on: the decode steps leave whatever an
    # inactive row computes in row 0
    eng.step()
    eng.step()
    assert eng._slot_req[0] is None and not b.done
    dead = int(np.argmax(np.asarray(eng._last[0])))
    # a successor whose own first token differs from the dead row's
    nxt = next(p for p in later if _greedy(model, p, 1)[0] != dead)
    c = eng.submit(nxt, max_new_tokens=4)
    eng.step()
    assert eng._slot_req[0] is c
    assert c.tokens == _greedy(model, nxt, 1) and c.tokens[0] != dead
    eng.run()
    assert c.tokens == _greedy(model, nxt, 4)
    assert b.tokens == _greedy(model, stays, 12)
    assert a.tokens == _greedy(model, first, 2)   # and grew no further
    if seating == "prefix-hit":
        # b forked a's third block, c adopted the two whole ones
        s = eng.stats()
        assert s["cow_forks"] == 1 and s["prefix_hit_tokens"] == 19 + 16


# ---- the planted fault of the benchmark's tests still lands ------------

def test_a_wrong_pick_program_alters_the_tokens_served(model):
    # tests/perf plants its fault by patching ``generate._pick_row``:
    # the step must look it up at call time, on an array whose last
    # axis is the vocabulary
    real = generate._pick_row

    def off_by_one(last, key, **kw):
        return (real(last, key, **kw) + 1) % last.shape[-1]

    prompt = _prompts(model, (6,))[0]
    eng = _engine(model, slots=2)
    with mock.patch.object(generate, "_pick_row", off_by_one):
        r = eng.submit(prompt, max_new_tokens=1)
        eng.run()
    want = _greedy(model, prompt, 1)[0]
    assert r.tokens == [(want + 1) % model[0].vocab_size]
