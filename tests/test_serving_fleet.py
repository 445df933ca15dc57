"""Serving fleet: affinity routing, stickiness, drain + kill migration.

The r13 fleet contract: prefix-similar traffic concentrates on one
replica (cache affinity), sessions stick, a draining replica sheds new
work while finishing old, and a killed replica's in-flight requests
migrate and complete bit-identically — never fail.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.controlplane.serving_fleet import (
    NoReadyReplica,
    ServingFleet,
    make_fleet_app,
)
from kubeflow_rm_tpu.controlplane.webapps.serving import (
    ReplicaUnavailable,
    ServingGateway,
    make_serving_app,
)
from kubeflow_rm_tpu.models import LlamaConfig, init_params
from kubeflow_rm_tpu.models.generate import (
    ContinuousBatchingEngine,
    generate_fused,
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _gateway(model, **kw):
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, slots=2, slot_len=32,
                                   block_size=4)
    kw.setdefault("admission", False)
    return ServingGateway(eng, **kw)


def _fleet(model, n=3, **kw):
    return ServingFleet({f"r{i}": _gateway(model) for i in range(n)},
                        **kw)


def _solo(model, prompt, budget):
    cfg, params = model
    ref = generate_fused(params, cfg, jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=budget, max_len=32)
    return np.asarray(ref)[0, len(prompt):].tolist()


def test_affinity_and_session_stickiness(model):
    fleet = _fleet(model)
    try:
        p = [5, 9, 2, 7, 1]
        # same prefix -> same replica, deterministically
        assert fleet.route(p) == fleet.route(p + [8, 8, 8])
        # a session key overrides the prefix key
        ka = fleet.affinity_key(p, "sess-a")
        assert ka == fleet.affinity_key([1], "sess-a")
        assert ka != fleet.affinity_key(p)
        # different prefixes eventually spread (not all on one replica)
        owners = {fleet.route([i * 3 + 1, i * 5 + 2, 7]) for i in
                  range(16)}
        assert len(owners) > 1
    finally:
        fleet.close()


def test_fleet_request_is_exact_and_prefix_cached(model):
    fleet = _fleet(model)
    try:
        p = [5, 9, 2, 7, 1, 1, 3]
        for _ in range(3):   # repeats land on the SAME replica's cache
            tokens, info = fleet.submit_and_wait("t", list(p),
                                                 max_new_tokens=6)
            assert tokens == _solo(model, p, 6)
            assert info["migrations"] == 0
        owner = fleet.route(p)
        hits = fleet.gateways[owner].engine.stats()["prefix_hit_tokens"]
        assert hits > 0
    finally:
        fleet.close()


def test_drain_sheds_new_work_and_healthz_flips(model):
    gw = _gateway(model)
    app = make_serving_app(gw, model[0])
    try:
        from werkzeug.test import Client
        c = Client(app)
        r = c.get("/healthz")
        assert r.status_code == 200 and r.get_json()["state"] == "ready"
        gw.start_drain()
        r = c.get("/healthz")
        assert r.status_code == 503
        assert r.get_json()["state"] == "draining"
        pending, reason = gw.try_submit("t", [1, 2, 3],
                                        max_new_tokens=2)
        assert pending is None and reason == "draining"
        assert c.post("/generate",
                      json={"prompt": [1, 2, 3]}).status_code == 503
    finally:
        gw.close()


def test_drain_evicts_queued_and_fleet_migrates(model):
    """Queued (not-yet-slotted) requests on a draining replica raise
    ReplicaUnavailable from wait(); through the fleet they resume
    elsewhere and return exact tokens."""
    fleet = _fleet(model, n=2)
    try:
        victim = fleet.route([5, 9, 2])
        gw = fleet.gateways[victim]
        # fill both slots + queue a third directly on the victim
        holders = [gw.try_submit("t", [7, 3, 1 + i],
                                 max_new_tokens=20)[0]
                   for i in range(2)]
        deadline = time.monotonic() + 30
        while (gw.engine.active_slots < 2
               and time.monotonic() < deadline):
            time.sleep(0.005)       # both holders must be slotted, so
        assert gw.engine.active_slots == 2      # the third stays queued
        queued, _ = gw.try_submit("t", [5, 9, 2], max_new_tokens=4)
        assert queued is not None
        fleet.drain(victim)
        with pytest.raises(ReplicaUnavailable):
            gw.wait(queued, timeout_s=5)
        # active slots finish on the draining replica
        for h in holders:
            assert len(gw.wait(h, timeout_s=60)) == 20
        # the fleet now routes the same prompt elsewhere and succeeds
        tokens, info = fleet.submit_and_wait("t", [5, 9, 2],
                                             max_new_tokens=4)
        assert tokens == _solo(model, [5, 9, 2], 4)
        assert fleet.states()[victim] == "draining"
        assert info["replicas"] and info["replicas"][0] != victim
    finally:
        fleet.close()


def test_kill_migrates_in_flight_to_exact_completion(model):
    """The chaos arm: kill the replica holding live requests; every
    one must migrate and produce the same tokens an uninterrupted run
    would have — zero failures."""
    fleet = _fleet(model)
    try:
        p = [5, 9, 2, 7, 1, 1, 3]
        want = _solo(model, p, 24)
        results = [None] * 5
        victim = fleet.route(p)

        def go(i):
            results[i] = fleet.submit_and_wait("t", list(p),
                                               max_new_tokens=24)

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(len(results))]
        for t in threads:
            t.start()
        # kill the moment the owner actually holds in-flight work
        gw = fleet.gateways[victim]
        deadline = time.monotonic() + 30
        while (not gw.engine.active_slots
               and time.monotonic() < deadline):
            time.sleep(0.001)
        assert gw.engine.active_slots
        fleet.kill(victim)
        for t in threads:
            t.join(timeout=60)
        migrated = 0
        for r in results:
            assert r is not None, "request hung"
            tokens, info = r
            assert tokens == want   # zero failures, bit-identical
            migrated += info["migrations"]
        assert migrated >= 1 and fleet.migrations >= 1
    finally:
        fleet.close()


def test_kill_resume_overflow_restarts_from_original_prompt(model):
    """A resume prompt (original + tokens_so_far) can round the prefill
    bucket past slot_len even though the original request fit:
    bucket(16) + 16 == slot_len exactly, so ANY resume with >= 1 token
    needs bucket 32 and cannot fit.  The fleet must restart such a
    request from the original prompt (greedy decode reproduces the same
    tokens) instead of failing it."""
    fleet = _fleet(model, n=2)
    try:
        p = [1 + (i % 9) for i in range(16)]
        want = _solo(model, p, 16)
        victim = fleet.route(p)
        gw = fleet.gateways[victim]
        result = {}

        # kill() needs the gateway's lock, which the drain thread
        # drops between steps for microseconds only (ROADMAP S10): on
        # a fast step loop the 16 tokens are through before the kill
        # gets in. Hold the thread off the lock for a moment a step.
        class _Dawdling(threading.Event):
            def is_set(self):
                time.sleep(0.003)
                return super().is_set()

        gw._stop = _Dawdling()

        def go():
            result["r"] = fleet.submit_and_wait("t", list(p),
                                                max_new_tokens=16)

        t = threading.Thread(target=go)
        t.start()
        # kill only once the request has produced tokens, so the
        # resume prompt is strictly longer than the original
        deadline = time.monotonic() + 30
        while (gw.snapshot()["decode_steps"] < 3
               and time.monotonic() < deadline):
            time.sleep(0.001)
        assert gw.snapshot()["decode_steps"] >= 3
        fleet.kill(victim)
        t.join(timeout=60)
        tokens, info = result["r"]
        assert tokens == want
        assert info["migrations"] >= 1
    finally:
        fleet.close()


def test_submit_reroutes_when_routed_replica_is_removed(model):
    """The remove_replica race: a request that routed to a replica an
    instant before ``remove_replica`` rebuilt the topology must
    re-resolve the ring AFTER the rebuild — never submit to (or crash
    on) the replica being removed."""
    fleet = _fleet(model, n=2)
    try:
        p = [5, 9, 2, 7, 1]
        want = _solo(model, p, 6)
        victim = fleet.route(p)
        orig_route = fleet.route
        removed = {}

        def racing_route(*a, **kw):
            name = orig_route(*a, **kw)
            if not removed and name == victim:
                # the topology rebuild lands between routing and
                # submit — exactly the window the bug lived in
                fleet.remove_replica(victim)
                removed["done"] = True
            return name

        fleet.route = racing_route
        tokens, info = fleet.submit_and_wait("t", list(p),
                                             max_new_tokens=6)
        assert removed, "race window never exercised"
        assert tokens == want
        assert victim not in info["replicas"]
        assert victim not in fleet.gateways
    finally:
        fleet.close()


def test_remove_replica_mid_flight_migrates_exactly(model):
    """Live shrink while the victim holds in-flight work: queued and
    active requests all migrate and complete bit-identically, and the
    victim is gone from the fleet afterwards."""
    fleet = _fleet(model, n=2)
    try:
        p = [5, 9, 2, 7, 1, 1, 3]
        want = _solo(model, p, 24)
        victim = fleet.route(p)
        results = [None] * 4

        def go(i):
            results[i] = fleet.submit_and_wait("t", list(p),
                                               max_new_tokens=24)

        threads = [threading.Thread(target=go, args=(i,))
                   for i in range(len(results))]
        for t in threads:
            t.start()
        gw = fleet.gateways[victim]
        deadline = time.monotonic() + 30
        while (not gw.engine.active_slots
               and time.monotonic() < deadline):
            time.sleep(0.001)
        assert gw.engine.active_slots
        fleet.remove_replica(victim)
        for t in threads:
            t.join(timeout=60)
        for r in results:
            assert r is not None, "request hung"
            tokens, _info = r
            assert tokens == want
        assert victim not in fleet.gateways
        assert victim not in fleet.states()
        assert len(fleet.states()) == 1
    finally:
        fleet.close()


def test_add_replica_joins_ring_and_serves(model):
    fleet = _fleet(model, n=1)
    try:
        with pytest.raises(ValueError):
            fleet.remove_replica("r0")   # never below one replica
        fleet.add_replica("r9", _gateway(model))
        with pytest.raises(ValueError):
            fleet.add_replica("r9", _gateway(model))  # dup name
        assert fleet.states() == {"r0": "ready", "r9": "ready"}
        # the newcomer takes real traffic: drain the original and the
        # fleet keeps serving, exactly
        fleet.drain("r0")
        p = [5, 9, 2]
        tokens, info = fleet.submit_and_wait("t", list(p),
                                             max_new_tokens=4)
        assert tokens == _solo(model, p, 4)
        assert info["replicas"] == ["r9"]
    finally:
        fleet.close()


def test_no_ready_replica_sheds(model):
    fleet = _fleet(model, n=1)
    try:
        fleet.drain("r0")
        with pytest.raises(NoReadyReplica):
            fleet.route([1, 2, 3])
        tokens, info = fleet.submit_and_wait("t", [1, 2, 3],
                                             max_new_tokens=2)
        assert tokens is None and info["reason"] == "no_replica"
    finally:
        fleet.close()


def test_fleet_app_surface(model):
    from werkzeug.test import Client

    fleet = _fleet(model, n=2)
    app = make_fleet_app(fleet, model[0])
    try:
        c = Client(app)
        r = c.get("/healthz")
        assert r.status_code == 200 and r.get_json()["ready"] == 2
        p = [5, 9, 2]
        r = c.post("/generate", json={"prompt": p, "max_new_tokens": 4,
                                      "session": "s1",
                                      "slo_class": "batch"})
        assert r.status_code == 200
        assert r.get_json()["tokens"] == _solo(model, p, 4)
        assert c.post("/generate", json={"prompt": "nope"}
                      ).status_code == 400
        assert c.post("/generate", json={"prompt": p,
                                         "slo_class": "gold"}
                      ).status_code == 400
        # ops drain endpoint pulls a replica out of the ring
        assert c.post("/replicas/r0/drain").status_code == 200
        assert c.post("/replicas/zz/drain").status_code == 404
        snap = c.get("/api/fleet").get_json()
        assert snap["replicas"]["r0"]["state"] == "draining"
        assert snap["replicas"]["r1"]["state"] == "ready"
        # one ready replica left: still healthy, still serving
        assert c.get("/healthz").get_json()["ready"] == 1
        r = c.post("/generate", json={"prompt": p, "max_new_tokens": 4})
        assert r.status_code == 200
    finally:
        fleet.close()
