"""The program measures its own hot loops: a stamped timeline on
every request however it was seated, the step loops' spans on the
profiler's clock (recorded while a session is open, with no switch of
their own), and the gateway's three request spans cut from the
engine's stamps."""

import pathlib
import sys
import time

import jax
import numpy as np
import pytest

from kubeflow_rm_tpu.controlplane import tracing
from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
from kubeflow_rm_tpu.controlplane.webapps.serving import ServingGateway
from kubeflow_rm_tpu.models import LlamaConfig, init_params
from kubeflow_rm_tpu.models.generate import ContinuousBatchingEngine
from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
from kubeflow_rm_tpu.training.loop import LoopConfig, fit
from kubeflow_rm_tpu.training.train import TrainConfig

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from perf import trace_reduce  # noqa: E402

PROMPT = [5, 9, 2, 7, 1, 8, 3, 4, 6]
# two whole blocks and half of a third (``block_size`` 8): a repeat
# adopts the two and forks the third copy-on-write
SHARED = list(range(1, 21))


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    return cfg, init_params(cfg, jax.random.key(0))


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("slots", 1)
    return ContinuousBatchingEngine(params, cfg, slot_len=64, block_size=8,
                                    **kw)


def _submit(model, eng, seating, prompt, budget=4):
    if seating == "chain-install":
        chain = _engine(model).prefill_chain(prompt)
        return eng.install_chain(chain, max_new_tokens=budget)
    return eng.submit(prompt, max_new_tokens=budget,
                      speculative=seating == "speculative",
                      slo_class="best_effort")


# ---- (a) the timeline, on all four ways a request is seated -----------

@pytest.mark.parametrize("seating", ["paged", "chain-install",
                                     "prefix-hit", "speculative"])
def test_every_finished_request_carries_an_ordered_timeline(model, seating):
    eng = _engine(model)
    before = time.perf_counter()
    # one slot: the second request waits for the first one's
    prompts = ([SHARED, SHARED] if seating == "prefix-hit"
               else [PROMPT, PROMPT[::-1]])
    reqs = [_submit(model, eng, seating, p) for p in prompts]
    assert all(r.t_submitted >= before and r.t_admitted is None
               for r in reqs)
    eng.run()
    for r in reqs:
        assert r.done and len(r.t_tokens) == len(r.tokens) == 4
        assert (r.t_submitted <= r.t_admitted <= r.t_first_token
                <= r.t_finished <= time.perf_counter())
        assert r.t_tokens == sorted(r.t_tokens)
        assert r.t_first_token == r.t_tokens[0]
        assert r.t_finished == r.t_tokens[-1]
        assert r.timeline()["t_tokens"] == r.t_tokens
    first, second = reqs
    assert second.t_admitted >= first.t_finished
    assert not hasattr(first, "submitted_step")
    assert not hasattr(first, "finished_step")
    if seating == "prefix-hit":
        stats = eng.stats()
        assert stats["cow_forks"] == 1 and stats["prefix_hit_tokens"] == 19


def test_a_request_sent_back_to_the_queue_keeps_no_admission_stamp(model):
    # the pool holds one request's blocks: the second is taken off the
    # queue, finds no blocks and waits at the front
    eng = _engine(model, slots=2, num_blocks=4)
    a = eng.submit(PROMPT, max_new_tokens=4)
    b = eng.submit(PROMPT[::-1], max_new_tokens=4)
    eng.step()
    assert a.t_admitted is not None and b.t_admitted is None
    eng.run()
    assert b.t_admitted >= a.t_finished


# ---- (b) the step loops' spans under a profiler session ---------------

def _session(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load(str(tmp_path))["host"]


def _spans(host, name):
    return sorted((s, s + d) for n, s, d in host if n == name)


def _inside(child, parents):
    return any(a <= child[0] and child[1] <= b for a, b in parents)


def test_engine_and_gateway_spans_on_the_profilers_clock(model, tmp_path):
    eng = _engine(model, slots=2)
    gw = ServingGateway(eng, admission=False)
    try:
        warm = gw.try_submit("t", PROMPT, max_new_tokens=2)[0]
        gw.wait(warm)                   # compiled before the session

        def body():
            pending = [gw.try_submit("t", p, max_new_tokens=6)[0]
                       for p in (PROMPT, PROMPT[::-1], PROMPT[2:])]
            for p in pending:
                gw.wait(p)
        host = _session(tmp_path, body)
    finally:
        gw.close()
    steps = _spans(host, "engine.step")
    assert len(steps) >= 6
    drains = _spans(host, "gateway.drain")
    for name in ("engine.admit", "engine.pick", "engine.dispatch",
                 "engine.scatter"):
        got = _spans(host, name)
        assert got and all(_inside(s, steps) for s in got), name
    assert all(_inside(s, drains) for s in steps)
    # the decode step's output is kept whole: the scatter span is still
    # there, once a dispatch, for step_idle_ms.scatter to read
    assert len(_spans(host, "engine.scatter")) \
        == len(_spans(host, "engine.dispatch"))
    prefills = _spans(host, "engine.prefill")
    assert prefills and all(
        _inside(s, _spans(host, "engine.admit")) for s in prefills)
    publishes = _spans(host, "gateway.publish")
    assert len(publishes) == len(drains)
    assert all(_inside(s, drains) and not _inside(s, steps)
               for s in publishes)
    # the four children of a step follow one another and do not overlap
    for a, b in steps:
        kids = sorted(s for name in ("engine.admit", "engine.pick",
                                     "engine.dispatch", "engine.scatter")
                      for s in _spans(host, name) if a <= s[0] and s[1] <= b)
        assert len(kids) in (2, 4)      # a step with no live slot: 2
        assert all(x[1] <= y[0] for x, y in zip(kids, kids[1:]))


def test_fit_spans_on_the_profilers_clock(tmp_path):
    cfg = TrainConfig(model=LlamaConfig.tiny())
    mesh = make_mesh(MeshConfig(fsdp=1), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)

    def batches():
        while True:
            tokens = rng.integers(0, cfg.model.vocab_size, (2, 32),
                                  dtype=np.int32)
            yield {"tokens": tokens, "labels": tokens}

    state, _ = fit(cfg, mesh, batches(), LoopConfig(total_steps=1))
    host = _session(tmp_path, lambda: fit(
        cfg, mesh, batches(), LoopConfig(total_steps=4, log_every=2),
        state=state))
    shard, step, log = (_spans(host, n) for n in (
        "train.shard_batch", "train.step", "train.log"))
    assert len(shard) == len(step) == 3 and len(log) == 2
    # one after the other, an iteration after an iteration
    order = sorted(shard + step + log)
    assert all(x[1] <= y[0] for x, y in zip(order, order[1:]))
    assert all(a[1] <= b[0] for a, b in zip(shard, step))


def test_no_session_no_span_and_no_switch():
    # outside a session an annotation records nothing and costs an
    # object and two calls: the same code path, no flag to forget
    from kubeflow_rm_tpu.utils.profiling import annotate
    t = time.perf_counter()
    for _ in range(1000):
        with annotate("engine.step"):
            pass
    assert (time.perf_counter() - t) / 1000 < 50e-6


# ---- (d) the fleet hands the stamps on; three request spans -----------

@pytest.fixture
def traced():
    tracing.collector().clear()
    tracing.set_enabled(True)
    yield tracing.collector()
    tracing.set_enabled(False)
    tracing.collector().clear()


def test_submit_and_wait_hands_the_timeline_on(model):
    gw = ServingGateway(_engine(model), admission=False)
    fleet = ServingFleet({"r0": gw})
    try:
        before = time.perf_counter()
        tokens, info = fleet.submit_and_wait("t", PROMPT, max_new_tokens=5)
        after = time.perf_counter()
    finally:
        fleet.close()
    tl = info["timeline"]
    assert (before <= tl["t_submitted"] <= tl["t_admitted"]
            <= tl["t_first_token"] <= tl["t_finished"] <= after)
    assert len(tl["t_tokens"]) == len(tokens) == 5
    assert tl["t_tokens"][0] == tl["t_first_token"]
    assert tl["t_tokens"][-1] == tl["t_finished"]


def test_three_request_spans_partition_submit_to_done(model, traced):
    gw = ServingGateway(_engine(model), admission=False)
    fleet = ServingFleet({"r0": gw})
    try:
        t0 = time.time()
        with tracing.start_span("client") as root:
            tokens, info = fleet.submit_and_wait("t", PROMPT,
                                                 max_new_tokens=5)
        t1 = time.time()
    finally:
        fleet.close()
    spans = {s["name"]: s for s in traced.get_trace(root.trace_id)}
    q, p, d = (spans[n] for n in ("serving.queue", "serving.prefill",
                                  "serving.decode"))
    assert q["parent_id"] == p["parent_id"] == d["parent_id"] == root.span_id
    assert q["end"] == p["start"] and p["end"] == d["start"]
    # the collector's epoch, from perf_counter stamps and one offset
    assert t0 - 0.05 <= q["start"] <= d["end"] <= t1 + 0.05
    tl = info["timeline"]
    assert d["end"] - q["start"] == pytest.approx(
        tl["t_finished"] - tl["t_submitted"], abs=1e-6)
    assert d["attrs"]["tokens"] == len(tokens)
