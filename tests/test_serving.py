"""Multi-tenant serving gateway: admission control, SLO shedding,
noisy-neighbor isolation, and the HTTP/metrics surface.

The engine's decode exactness lives in tests/test_generate.py; here we
test the POLICY layer around it — what gets admitted, what gets shed
with which reason/status, and that one tenant's storm cannot consume
another tenant's admission capacity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_rm_tpu.controlplane.deploy.kubeclient import TokenBucket
from kubeflow_rm_tpu.controlplane.webapps.serving import (
    ServingGateway,
    TenantPolicy,
    make_serving_app,
)
from kubeflow_rm_tpu.models import (
    ContinuousBatchingEngine,
    LlamaConfig,
    generate_fused,
    init_params,
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("slots", 2)
    kw.setdefault("slot_len", 32)
    return ContinuousBatchingEngine(params, cfg, **kw)


# -- TokenBucket.try_acquire (the non-blocking admission primitive) ---------


def test_token_bucket_try_acquire_refills_on_injected_clock():
    t = {"now": 0.0}
    b = TokenBucket(qps=2.0, burst=4, clock=lambda: t["now"])
    assert all(b.try_acquire(1.0) for _ in range(4))   # burst drains
    assert not b.try_acquire(1.0)                      # empty: shed
    assert b.throttled_calls == 1
    t["now"] += 1.0                                    # +2 tokens
    assert b.try_acquire(2.0)
    assert not b.try_acquire(0.5)
    t["now"] += 100.0                                  # refill caps at burst
    assert b.try_acquire(4.0) and not b.try_acquire(0.5)


def test_token_bucket_try_acquire_weighted():
    """Weighted acquire is the token-budget denomination: a 16-token
    generation spends 16 units."""
    t = {"now": 0.0}
    b = TokenBucket(qps=10.0, burst=20, clock=lambda: t["now"])
    assert b.try_acquire(16.0)
    assert not b.try_acquire(16.0)      # only 4 left
    assert b.try_acquire(4.0)


# -- gateway admission + shedding -------------------------------------------


def test_gateway_sheds_over_rate_tenant(model):
    t = {"now": 0.0}
    gw = ServingGateway(
        _engine(model),
        default_policy=TenantPolicy(qps=1.0, burst=2),
        clock=lambda: t["now"])
    try:
        oks, reasons = [], []
        for _ in range(4):
            pending, reason = gw.try_submit("noisy", [1, 2, 3],
                                            max_new_tokens=2)
            (oks if pending else reasons).append(reason)
        assert len(oks) == 2 and reasons == ["rate", "rate"]
        assert gw.shed_counts == {"rate": 2}
        t["now"] += 1.0                   # bucket refills: admitted again
        pending, reason = gw.try_submit("noisy", [1, 2, 3],
                                        max_new_tokens=2)
        assert pending is not None
    finally:
        gw.close()


def test_gateway_sheds_over_token_budget(model):
    gw = ServingGateway(
        _engine(model),
        default_policy=TenantPolicy(qps=1000.0, burst=1000,
                                    tokens_per_s=1.0, token_burst=20),
        clock=lambda: 0.0)
    try:
        pending, _ = gw.try_submit("t", [1], max_new_tokens=16)
        assert pending is not None
        pending, reason = gw.try_submit("t", [1], max_new_tokens=16)
        assert pending is None and reason == "tokens"
        # a small ask still fits the remaining budget
        pending, _ = gw.try_submit("t", [1], max_new_tokens=4)
        assert pending is not None
    finally:
        gw.close()


def test_gateway_queue_cap_survives_admission_off(model):
    gw = ServingGateway(_engine(model), max_queue=0, admission=False)
    try:
        pending, reason = gw.try_submit("t", [1, 2], max_new_tokens=2)
        assert pending is None and reason == "queue"
        assert gw.shed_counts == {"queue": 1}
    finally:
        gw.close()


def test_gateway_slo_projection_sheds(model):
    gw = ServingGateway(
        _engine(model),
        default_policy=TenantPolicy(slo_p95_ms=50.0))
    try:
        gw._ema_ms = 1000.0               # recent service times >> SLO
        pending, reason = gw.try_submit("t", [1, 2], max_new_tokens=2)
        assert pending is None and reason == "slo"
    finally:
        gw.close()


def test_gateway_admission_off_admits_everything(model):
    gw = ServingGateway(
        _engine(model),
        default_policy=TenantPolicy(qps=0.001, burst=1, slo_p95_ms=1.0),
        admission=False)
    try:
        gw._ema_ms = 1e6
        for _ in range(3):
            pending, reason = gw.try_submit("t", [1, 2],
                                            max_new_tokens=2)
            assert pending is not None and reason is None
    finally:
        gw.close()


def test_noisy_neighbor_cannot_starve_victim(model):
    """Per-tenant buckets are the isolation mechanism: a flooding
    tenant exhausts ITS bucket, not the victim's."""
    t = {"now": 0.0}
    gw = ServingGateway(
        _engine(model),
        default_policy=TenantPolicy(qps=1.0, burst=3),
        clock=lambda: t["now"])
    try:
        flood_ok = sum(
            gw.try_submit("flood", [1], max_new_tokens=1)[0] is not None
            for _ in range(20))
        victim_ok = sum(
            gw.try_submit("victim", [1], max_new_tokens=1)[0] is not None
            for _ in range(3))
        assert flood_ok == 3              # flood capped at its burst
        assert victim_ok == 3             # victim's bucket untouched
        assert gw.shed_counts["rate"] == 17
    finally:
        gw.close()


# -- end-to-end: decode through the gateway + observability -----------------


def test_gateway_decodes_exactly_and_reports(model):
    cfg, params = model
    engine = _engine(model)
    gw = ServingGateway(engine)
    try:
        prompt = [5, 9, 2]
        pending, reason = gw.try_submit("alice", prompt,
                                        max_new_tokens=6)
        assert reason is None
        tokens = gw.wait(pending, timeout_s=120)
        ref = generate_fused(params, cfg, jnp.asarray([prompt]),
                             max_new_tokens=6, max_len=engine.slot_len)
        assert tokens == np.asarray(ref[0, len(prompt):]).tolist()

        lat = gw.tenant_latency("alice")
        assert lat["count"] == 1 and lat["p95_ms"] > 0
        snap = gw.snapshot()
        assert snap["slot_capacity"] == engine.slots
        assert snap["finished_total"] == 1
        assert "alice" in snap["tenants"]
    finally:
        gw.close()


def test_serving_app_http_surface(model):
    cfg, params = model
    from werkzeug.test import Client

    gw = ServingGateway(
        _engine(model),
        default_policy=TenantPolicy(qps=0.001, burst=2),
        clock=None)
    try:
        c = Client(make_serving_app(gw, cfg))
        r = c.post("/generate", json={"prompt": [1, 2, 3], "tenant": "a",
                                      "max_new_tokens": 4})
        assert r.status_code == 200
        body = r.get_json()
        assert len(body["tokens"]) == 4 and body["latency_ms"] > 0

        r = c.post("/generate", json={"prompt": [1], "tenant": "a"},
                   headers={"X-Tenant": "ignored-when-body-has-tenant"})
        assert r.status_code == 200
        # bucket (burst 2) is now empty: rate sheds map to 429
        r = c.post("/generate", json={"prompt": [1], "tenant": "a"})
        assert r.status_code == 429
        assert r.get_json()["reason"] == "rate"
        assert r.headers["Retry-After"] == "1"

        # validation 400s
        assert c.post("/generate", json={"prompt": []}).status_code == 400
        assert c.post("/generate",
                      json={"prompt": [cfg.vocab_size]}).status_code == 400
        assert c.post("/generate",
                      json={"prompt": [1], "max_new_tokens": 0}
                      ).status_code == 400
        # capacity guard surfaces as 400, not a 500
        assert c.post("/generate",
                      json={"prompt": [1] * 30, "tenant": "b",
                            "max_new_tokens": 30}).status_code == 400

        assert c.get("/healthz").status_code == 200
        api = c.get("/api/metrics").get_json()["serving"]
        assert api["shed"].get("rate") == 1
        assert "a" in api["tenants"]
        scrape = c.get("/metrics").get_data(as_text=True)
        assert "serving_requests_total" in scrape
        assert "serving_shed_total" in scrape
    finally:
        gw.close()


def test_gateway_concurrent_tenants_all_complete(model):
    """Many waiters against few slots: everything admitted completes,
    occupancy is accounted, and per-tenant latency windows fill."""
    import threading

    cfg, params = model
    gw = ServingGateway(_engine(model))
    results = {}

    def one(name, n):
        prompt = [(n * 7 + 3) % (cfg.vocab_size - 1) + 1] * (2 + n % 5)
        pending, reason = gw.try_submit(name, prompt, max_new_tokens=3)
        assert reason is None
        results[name] = gw.wait(pending, timeout_s=120)

    try:
        ts = [threading.Thread(target=one, args=(f"t{i}", i))
              for i in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(results) == 6
        assert all(len(v) == 3 for v in results.values())
        snap = gw.snapshot()
        assert snap["finished_total"] == 6
        assert 0 < snap["batch_occupancy"] <= 1.0
    finally:
        gw.close()


# -- engine failure: waiters wake with the cause, replica leaves service ----


class _BrokenEngine:
    """Engine stub: admits like the real one, then ``step`` raises (a
    compile error or device OOM, as the drain thread would see it)."""

    slots = 2

    def __init__(self):
        self.queued = []

    @property
    def queue_depth(self):
        return len(self.queued)

    active_slots = 0

    def submit(self, prompt, **kw):
        req = type("Req", (), {"done": False, "tokens": []})()
        self.queued.append(req)
        return req

    def step(self):
        raise MemoryError("RESOURCE_EXHAUSTED: injected device OOM")

    def stats(self):
        return {"queue_depth": self.queue_depth, "active_slots": 0,
                "slots": self.slots, "batch_occupancy": 0.0,
                "decode_steps": 0, "finished_total": 0}


def test_engine_step_failure_fails_waiters_and_marks_replica():
    from werkzeug.test import Client

    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import EngineFailed

    gw = ServingGateway(_BrokenEngine(), admission=False)
    fleet = ServingFleet({"r0": gw}, prefix_tokens=4)
    try:
        # the waiter wakes at once with the engine's error as the
        # cause — not after wait()'s 300 s timeout on a dead thread
        with pytest.raises(EngineFailed) as exc:
            fleet.submit_and_wait("t", [1, 2, 3], max_new_tokens=4,
                                  timeout_s=30.0)
        assert isinstance(exc.value.__cause__, MemoryError)
        assert isinstance(gw.error, MemoryError)
        assert gw.snapshot()["error"].startswith("MemoryError")
        # out of service: later submits shed with the reason, and the
        # health check a router polls says so
        assert gw.try_submit("t", [1], max_new_tokens=1) == (None, "failed")
        tokens, info = fleet.submit_and_wait("t", [1], max_new_tokens=1)
        assert tokens is None and info["reason"] == "no_replica"
        resp = Client(make_serving_app(gw, LlamaConfig.tiny())).get(
            "/healthz")
        assert resp.status_code == 503
        assert resp.get_json()["state"] == "failed"
    finally:
        fleet.close()
