"""Multi-tenant serving gateway over the continuous-batching engine.

The serving analogue of what the provision path grew in PRs 3-5: the
decode engine (``models.generate.ContinuousBatchingEngine``) gives us
slot-level admission/retirement at token boundaries; this module puts
a tenant-aware front door on it so one tenant's storm cannot blow
another's p95 — the failure mode static batches have no answer to.

Admission control, in order (first failure sheds the request before it
ever touches the engine):

1. **Request rate** — per-tenant ``TokenBucket.try_acquire(1)``
   (the same client-go-style bucket kubeclient throttles writes with,
   non-blocking: an over-rate request is shed with 429 immediately
   instead of queueing into everyone else's latency).
2. **Token budget** — a second per-tenant bucket denominated in
   TOKENS (``try_acquire(max_new_tokens)``): a tenant asking for long
   generations spends its budget proportionally.
3. **Queue cap** — a bounded engine queue; beyond it, 503.
4. **p95 SLO projection** — shed (503) when the queue-depth-scaled
   EMA of recent request service times projects past the configured
   SLO: ``(queue/slots + 1) * ema_ms > slo_ms``. This is what keeps
   ACCEPTED requests inside the SLO under overload: the gateway sheds
   load instead of violating latency.

Everything is observable: queue depth, batch occupancy, per-tenant
request/shed counters and latency histograms land in the control-plane
prometheus registry (``controlplane/metrics.py``), flow into the
dashboard's ``/api/metrics`` controlplane section
(``webapps/metrics_service._controlplane_section``), and are also
served directly by this app's own ``/metrics`` + ``/api/metrics``
routes — the serving pod is scrape-compatible with the rest of the
platform.

API: ``POST /generate {"prompt": [ids...], "tenant"?: "name",
"max_new_tokens"?: n}`` → ``{"tokens": [ids...], "latency_ms": ...}``;
``GET /healthz``; ``GET /metrics`` (prometheus text);
``GET /api/metrics`` (the serving JSON section).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from dataclasses import dataclass

from kubeflow_rm_tpu.controlplane import metrics as cp_metrics
from kubeflow_rm_tpu.controlplane import tracing
from kubeflow_rm_tpu.controlplane.deploy.kubeclient import TokenBucket
from kubeflow_rm_tpu.analysis.lockgraph import make_lock
from kubeflow_rm_tpu.utils.profiling import annotate

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class TenantPolicy:
    """Per-tenant admission knobs. ``qps``/``burst`` bound request
    RATE; ``tokens_per_s``/``token_burst`` bound decoded-token SPEND;
    ``slo_p95_ms`` is the latency promise the gateway sheds to keep;
    ``slo_class`` is the engine queue the tenant's admitted requests
    drain from (interactive | batch | best_effort)."""
    qps: float = 20.0
    burst: int = 40
    tokens_per_s: float = 2000.0
    token_burst: int = 4000
    slo_p95_ms: float = 2000.0
    slo_class: str = "interactive"


class ReplicaUnavailable(Exception):
    """The replica gave this request up before finishing it (drain or
    death). The request is NOT failed — the caller (serving fleet, or
    any retrying client) resubmits it elsewhere and the generation
    resumes from the tokens already produced."""

    def __init__(self, msg: str, tokens_so_far=None):
        super().__init__(msg)
        self.tokens_so_far = list(tokens_so_far or [])


class EngineFailed(RuntimeError):
    """``engine.step()`` raised (compile error, device OOM, a bug): the
    replica is out of service and every request it held fails with this
    error, chained to the cause. Not retried elsewhere — a program that
    does not compile here does not compile on the next replica."""


class _Pending:
    """A request in flight: the HTTP thread parks on ``event`` while
    the drain thread decodes."""

    __slots__ = ("req", "tenant", "event", "t_done", "trace", "failed",
                 "error")

    def __init__(self, req, tenant, trace=None):
        self.req = req
        self.tenant = tenant
        self.event = threading.Event()
        # when the drain thread let go of the request, on the clock of
        # the engine's own stamps (``req.t_submitted`` is the start)
        self.t_done = None
        # set when the replica abandons the request (drain/close)
        # before the engine finishes it — wait() then raises
        # ReplicaUnavailable instead of returning a torn result
        self.failed = False
        # set when the engine itself failed under this request —
        # wait() then raises EngineFailed from it
        self.error = None
        # traceparent of the admitting request, if it carried one —
        # the drain thread records the request's spans against it
        self.trace = trace


class ServingGateway:
    """Admission control + drain loop around one decode engine.

    ``admission=False`` turns checks 1/2/4 off (the noisy-neighbor A/B
    baseline arm: everything is admitted, victims eat the flood). The
    queue cap stays on in both arms — an unbounded queue is an OOM,
    not a policy choice.
    """

    def __init__(self, engine, *, policies: dict | None = None,
                 default_policy: TenantPolicy | None = None,
                 max_queue: int = 64, admission: bool = True,
                 clock=None):
        self.engine = engine
        self.policies = dict(policies or {})
        self.default_policy = default_policy or TenantPolicy()
        self.max_queue = max_queue
        self.admission = admission
        self._clock = clock or time.monotonic
        # turns a perf_counter stamp into the span collector's epoch
        self._epoch_offset = time.time() - time.perf_counter()
        self._lock = make_lock("serving.gateway")  # engine + pending
        self._rate_buckets: dict[str, TokenBucket] = {}
        self._token_buckets: dict[str, TokenBucket] = {}
        self._pending: list[_Pending] = []
        # sliding per-tenant latency windows for p95 reporting, plus
        # the EMA the SLO projection sheds on
        self._lat_windows: dict[str, list[float]] = {}
        # per-tenant slowest traced request — the exemplar id reported
        # next to the latency summary so "p95 is bad" links straight
        # to a trace you can pull from /api/traces/<id>
        self._exemplars: dict[str, dict] = {}
        self._ema_ms: float | None = None
        self.shed_counts: dict[str, int] = {}
        self.draining = False
        # the exception that took the engine down, if one did: the
        # replica then sheds with reason "failed" and healthz says so
        self.error: Exception | None = None
        self._stop = threading.Event()
        cp_metrics.SERVING_SLOT_CAPACITY.set(engine.slots)
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    # -- policy plumbing ---------------------------------------------------

    def _policy(self, tenant: str) -> TenantPolicy:
        return self.policies.get(tenant, self.default_policy)

    def _buckets(self, tenant: str) -> tuple[TokenBucket, TokenBucket]:
        if tenant not in self._rate_buckets:
            pol = self._policy(tenant)
            self._rate_buckets[tenant] = TokenBucket(
                pol.qps, pol.burst, clock=self._clock)
            self._token_buckets[tenant] = TokenBucket(
                pol.tokens_per_s, pol.token_burst, clock=self._clock)
        return self._rate_buckets[tenant], self._token_buckets[tenant]

    # -- admission ---------------------------------------------------------

    def _shed(self, tenant: str, reason: str) -> None:
        cp_metrics.SERVING_SHED_TOTAL.labels(tenant, reason).inc()
        cp_metrics.SERVING_REQUESTS_TOTAL.labels(tenant, "shed").inc()
        self.shed_counts[reason] = self.shed_counts.get(reason, 0) + 1

    def _shed_closed(self, tenant: str, sp) -> tuple[None, str]:
        """Shed on a replica that is out of rotation, saying why."""
        reason = "failed" if self.error else "draining"
        self._shed(tenant, reason)
        sp.set_attr("shed", reason)
        return None, reason

    def try_submit(self, tenant: str, prompt: list[int], *,
                   max_new_tokens: int, eos_id: int | None = None,
                   slo_class: str | None = None,
                   speculative: bool = False, chain=None
                   ) -> tuple[_Pending | None, str | None]:
        """Admit or shed. Returns (pending, None) on admit,
        (None, reason) on shed — reason in
        rate|tokens|queue|slo|draining|failed. ``slo_class`` overrides the
        tenant policy's default engine queue. ``chain`` is an exported
        prefix chain (from a prefill replica / the global store): the
        engine seats it directly via ``install_chain`` and skips
        prefill entirely. ``speculative`` routes the request through
        the fused speculative-decode path (batch/best_effort only)."""
        pol = self._policy(tenant)
        trace = tracing.current_traceparent()
        with tracing.start_span_if_active(
                "serving.admit", attrs={"tenant": tenant}) as sp:
            if self.draining:
                return self._shed_closed(tenant, sp)
            if self.admission:
                rate, budget = self._buckets(tenant)
                if not rate.try_acquire(1.0):
                    self._shed(tenant, "rate")
                    sp.set_attr("shed", "rate")
                    return None, "rate"
                if not budget.try_acquire(float(max_new_tokens)):
                    self._shed(tenant, "tokens")
                    sp.set_attr("shed", "tokens")
                    return None, "tokens"
            with self._lock:
                # re-check under the lock: a drain/close that began
                # after the fast-path check above must not let this
                # request enqueue onto a stopping replica (it would
                # never be drained OR failed — a silent hang)
                if self.draining:
                    return self._shed_closed(tenant, sp)
                depth = self.engine.queue_depth
                if depth >= self.max_queue:
                    self._shed(tenant, "queue")
                    sp.set_attr("shed", "queue")
                    return None, "queue"
                if self.admission and self._ema_ms is not None:
                    projected = (depth / self.engine.slots + 1.0) \
                        * self._ema_ms
                    if projected > pol.slo_p95_ms:
                        self._shed(tenant, "slo")
                        sp.set_attr("shed", "slo")
                        return None, "slo"
                if chain is not None:
                    req = self.engine.install_chain(
                        chain, max_new_tokens=max_new_tokens,
                        eos_id=eos_id,
                        slo_class=slo_class or pol.slo_class)
                else:
                    req = self.engine.submit(
                        prompt, max_new_tokens=max_new_tokens,
                        eos_id=eos_id,
                        slo_class=slo_class or pol.slo_class,
                        speculative=speculative)
                pending = _Pending(req, tenant, trace=trace)
                self._pending.append(pending)
                cp_metrics.SERVING_QUEUE_DEPTH.set(
                    self.engine.queue_depth)
        return pending, None

    # -- disaggregated-serving surface -------------------------------------
    # A prefill replica runs ``prefill_chain`` (compute + export, no
    # decode slot consumed); decode replicas ``adopt_chain`` (seat a
    # store-served chain into the local pool) or install it per-request
    # via ``try_submit(chain=...)``. All three hold the gateway lock:
    # they touch the same engine the drain thread steps.

    def prefill_chain(self, prompt: list[int]):
        """Run prefill into cache blocks and export the serialized
        chain (see ``models.paging.export_chain``). Returns None when
        draining or the pool is too full to hold it."""
        with self._lock:
            if self.draining:
                return None
            return self.engine.prefill_chain(prompt)

    def adopt_chain(self, chain) -> int:
        """Seat an exported chain into the local block pool (no
        request attached). Returns blocks imported (0 = already local,
        pool full, or draining)."""
        with self._lock:
            if self.draining:
                return 0
            return self.engine.adopt_chain(chain)

    def chain_coverage(self, prompt: list[int]) -> int:
        """Tokens of ``prompt`` already covered by locally-resident
        prefix blocks — the fleet uses this to decide whether routing
        through the prefill tier would save anything."""
        with self._lock:
            return self.engine.chain_coverage(prompt)

    def device_counters(self) -> dict:
        """The engine's on-device counters (``ContinuousBatchingEngine
        .device_counters``: one blocking transfer), under the lock: the
        cache that holds them is donated by every step of the drain
        thread. For a reader at a window's two ends, not a step."""
        with self._lock:
            return self.engine.device_counters()

    def wait(self, pending: _Pending, timeout_s: float = 300.0
             ) -> list[int]:
        if not pending.event.wait(timeout_s):
            raise TimeoutError("generation timed out")
        if pending.error is not None:
            raise EngineFailed(
                f"engine step failed: {pending.error!r}"
            ) from pending.error
        if pending.failed and not pending.req.done:
            raise ReplicaUnavailable(
                "replica gave up this request mid-flight "
                "(drain or shutdown) — resubmit elsewhere",
                tokens_so_far=pending.req.tokens)
        lat_s = pending.t_done - pending.req.t_submitted
        tenant = pending.tenant
        cp_metrics.SERVING_REQUESTS_TOTAL.labels(tenant, "ok").inc()
        cp_metrics.SERVING_REQUEST_LATENCY_SECONDS.labels(
            tenant).observe(lat_s)
        cp_metrics.SERVING_GENERATED_TOKENS_TOTAL.labels(tenant).inc(
            len(pending.req.tokens))
        return pending.req.tokens

    # -- drain loop --------------------------------------------------------

    def _drain(self) -> None:
        while not self._stop.is_set():
            ready = []
            with self._lock:
                busy = (self.engine.queue_depth
                        or self.engine.active_slots)
                if busy:
                    with annotate("gateway.drain"):
                        ready = self._step_locked()
            if ready is None:       # the engine failed: out of service
                return
            now = time.perf_counter()
            for p in ready:
                self._complete(p, now)
            if not busy:
                self._stop.wait(0.001)

    def _step_locked(self) -> list[_Pending] | None:
        """One engine step and what follows it, under the lock: the
        gauges, and the pendings whose requests are done. None where
        the step raised."""
        try:
            finished = self.engine.step()
        except Exception as e:
            # the engine's state is unknown past this point: take the
            # replica out of service and wake every waiter with the
            # cause, instead of dying silently and leaving them to
            # their timeouts
            log.exception("engine step failed; replica out of service")
            self.error = e
            self.draining = True
            orphans, self._pending = self._pending, []
            for p in orphans:
                p.error = e
                p.t_done = time.perf_counter()
                p.event.set()
            return None
        with annotate("gateway.publish"):
            stats = self.engine.stats()
            cp_metrics.SERVING_QUEUE_DEPTH.set(stats["queue_depth"])
            cp_metrics.SERVING_ACTIVE_SLOTS.set(stats["active_slots"])
            cp_metrics.SERVING_BATCH_OCCUPANCY.set(
                stats["batch_occupancy"])
            for c, d in stats.get("queue_depth_by_class", {}).items():
                cp_metrics.SERVING_CLASS_QUEUE_DEPTH.labels(c).set(d)
            cp_metrics.SERVING_FREE_BLOCK_FRACTION.set(
                stats["free_block_fraction"])
            if stats["prompt_tokens"]:
                hr = stats["prefix_hit_ratio"]
                cp_metrics.SERVING_PREFIX_HIT_RATIO.set(hr)
                cp_metrics.SERVING_PREFIX_MISS_RATIO.set(1.0 - hr)
        if not finished:
            return []
        ready = [p for p in self._pending if p.req.done]
        self._pending = [p for p in self._pending if not p.req.done]
        return ready

    def _complete(self, p: _Pending, now: float) -> None:
        """Off the lock: the request's latency into the tenant's
        window and the SLO's EMA, its spans where it was traced, then
        wake its waiter."""
        p.t_done = now
        lat_ms = (p.t_done - p.req.t_submitted) * 1e3
        window = self._lat_windows.setdefault(p.tenant, [])
        window.append(lat_ms)
        del window[:-256]
        self._ema_ms = (lat_ms if self._ema_ms is None else
                        0.8 * self._ema_ms + 0.2 * lat_ms)
        if p.trace is not None:
            # retroactive spans from the engine's stamps, parented on
            # the admitting request so they join its trace: together
            # they partition submit -> done
            req, off = p.req, self._epoch_offset
            attrs = {"tenant": p.tenant, "tokens": len(req.tokens)}
            for name, start, end in (
                    ("serving.queue", req.t_submitted, req.t_admitted),
                    ("serving.prefill", req.t_admitted, req.t_first_token),
                    ("serving.decode", req.t_first_token, req.t_finished)):
                if start is not None and end is not None:   # no token
                    tracing.record_span(name, start=start + off,
                                        end=end + off, parent=p.trace,
                                        attrs=attrs)
            ctx = tracing.parse_traceparent(p.trace)
            ex = self._exemplars.get(p.tenant)
            if ctx is not None and (ex is None
                                    or lat_ms > ex["latency_ms"]):
                self._exemplars[p.tenant] = {
                    "trace_id": ctx.trace_id,
                    "latency_ms": round(lat_ms, 3)}
        p.event.set()

    def start_drain(self) -> list[_Pending]:
        """Begin pulling this replica out of rotation: new submits
        shed with reason ``draining`` (healthz flips 503 so LBs stop
        routing here), QUEUED requests are evicted and handed back to
        the caller for re-routing (their ``wait`` raises
        ``ReplicaUnavailable``), and requests already holding a decode
        slot finish normally. Returns the evicted pendings."""
        with self._lock:
            self.draining = True
            evicted_reqs = {id(r) for r in self.engine.evict_queued()}
            evicted = [p for p in self._pending
                       if id(p.req) in evicted_reqs]
            self._pending = [p for p in self._pending
                             if id(p.req) not in evicted_reqs]
            cp_metrics.SERVING_QUEUE_DEPTH.set(self.engine.queue_depth)
        for p in evicted:
            p.failed = True
            p.t_done = time.perf_counter()
            p.event.set()
        return evicted

    def close(self) -> None:
        with self._lock:
            # flip draining first so a submit racing with close sheds
            # instead of enqueueing onto the stopped drain thread
            self.draining = True
            orphans = list(self._pending)
            self._pending = []
        self._stop.set()
        self._thread.join(timeout=5)
        for p in orphans:         # fail any orphans; a request the
            p.failed = True       # engine DID finish stays ok (wait
            p.t_done = time.perf_counter()   # checks req.done first)
            p.event.set()

    # -- observability -----------------------------------------------------

    def tenant_latency(self, tenant: str) -> dict:
        window = sorted(self._lat_windows.get(tenant, []))
        if not window:
            return {"count": 0, "p50_ms": None, "p95_ms": None,
                    "slowest_trace": self._exemplars.get(tenant)}
        return {
            "count": len(window),
            "p50_ms": window[int(0.50 * (len(window) - 1))],
            "p95_ms": window[int(0.95 * (len(window) - 1))],
            # exemplar: the slowest TRACED request seen for this tenant
            # — resolves via GET /api/traces/<trace_id>
            "slowest_trace": self._exemplars.get(tenant),
        }

    def snapshot(self) -> dict:
        stats = self.engine.stats()
        return {
            "admission": self.admission,
            "draining": self.draining,
            "error": repr(self.error) if self.error else None,
            "queue_depth_by_class": stats.get("queue_depth_by_class"),
            "prefix_hit_ratio": stats.get("prefix_hit_ratio"),
            "free_block_fraction": stats.get("free_block_fraction"),
            "cow_forks": stats.get("cow_forks"),
            "queue_depth": stats["queue_depth"],
            "active_slots": stats["active_slots"],
            "slot_capacity": stats["slots"],
            "batch_occupancy": stats["batch_occupancy"],
            "decode_steps": stats["decode_steps"],
            "finished_total": stats["finished_total"],
            "shed": dict(self.shed_counts),
            "ema_service_ms": self._ema_ms,
            "tenants": {t: self.tenant_latency(t)
                        for t in sorted(self._lat_windows)},
        }


def make_serving_app(gateway: ServingGateway, cfg):
    """werkzeug WSGI app over a gateway: the tenant-facing front door.

    Requests carry a ``tenant`` field (header ``X-Tenant`` also
    accepted — the auth companion injects it in-cluster); sheds map to
    429 (per-tenant rate/budget — the client should back off) or 503
    (gateway-wide queue/SLO pressure — retry against another replica).
    """
    from werkzeug.exceptions import BadRequest, HTTPException
    from werkzeug.routing import Map, Rule
    from werkzeug.wrappers import Request, Response

    urls = Map([Rule("/generate", endpoint="generate", methods=["POST"]),
                Rule("/healthz", endpoint="healthz"),
                Rule("/metrics", endpoint="metrics"),
                Rule("/api/metrics", endpoint="api_metrics")])

    def _json(payload, status=200):
        return Response(json.dumps(payload), status=status,
                        content_type="application/json")

    def app(environ, start_response):
        # same server-span contract as WebApp: context-bearing requests
        # join their caller's trace (admission + parked wait happen
        # inside; the decode span is stamped by the drain thread)
        if tracing.enabled():
            parent = tracing.parse_traceparent(
                environ.get("HTTP_TRACEPARENT"))
            if parent is not None:
                with tracing.start_span(
                        f"{environ.get('REQUEST_METHOD', 'GET')} "
                        f"{environ.get('PATH_INFO', '/')}",
                        kind="server", parent=parent,
                        attrs={"component": "serving"}):
                    return _app_inner(environ, start_response)
        return _app_inner(environ, start_response)

    def _app_inner(environ, start_response):
        req = Request(environ)
        try:
            endpoint, _ = urls.bind_to_environ(environ).match()
            if endpoint == "healthz":
                # a draining replica must fail its health check BEFORE
                # its queue is severed, so routers/LBs stop sending new
                # work while in-flight requests still finish here
                if gateway.draining:
                    state = "failed" if gateway.error else "draining"
                    return _json({"ok": False, "state": state},
                                 status=503)(environ, start_response)
                return _json({"ok": True, "state": "ready"})(
                    environ, start_response)
            if endpoint == "metrics":
                resp = Response(cp_metrics.scrape(),
                                content_type="text/plain; version=0.0.4")
                return resp(environ, start_response)
            if endpoint == "api_metrics":
                return _json({"serving": gateway.snapshot()})(
                    environ, start_response)
            body = req.get_json(force=True)
            if not isinstance(body, dict):
                raise BadRequest("body must be a JSON object")
            prompt = body.get("prompt")
            if (not isinstance(prompt, list) or not prompt
                    or not all(isinstance(t, int)
                               and 0 <= t < cfg.vocab_size
                               for t in prompt)):
                raise BadRequest("prompt must be a non-empty list of "
                                 f"token ids in [0, {cfg.vocab_size})")
            tenant = body.get("tenant") \
                or req.headers.get("X-Tenant") or "default"
            if not isinstance(tenant, str) or len(tenant) > 64:
                raise BadRequest("tenant must be a short string")
            max_new = body.get("max_new_tokens", 16)
            if not isinstance(max_new, int) or not 1 <= max_new <= 4096:
                raise BadRequest("max_new_tokens must be an int in "
                                 "[1, 4096]")
            eos_id = body.get("eos_id")
            if eos_id is not None and not isinstance(eos_id, int):
                raise BadRequest("eos_id must be an int")
            slo_class = body.get("slo_class")
            if slo_class is not None and slo_class not in (
                    "interactive", "batch", "best_effort"):
                raise BadRequest("slo_class must be one of "
                                 "interactive|batch|best_effort")
            speculative = body.get("speculative", False)
            if not isinstance(speculative, bool):
                raise BadRequest("speculative must be a bool")
            try:
                pending, reason = gateway.try_submit(
                    tenant, prompt, max_new_tokens=max_new,
                    eos_id=eos_id, slo_class=slo_class,
                    speculative=speculative)
            except ValueError as e:   # request cannot fit a slot
                raise BadRequest(str(e)) from e
            if pending is None:
                status = 429 if reason in ("rate", "tokens") else 503
                resp = _json({"error": "shed", "reason": reason},
                             status=status)
                resp.headers["Retry-After"] = "1"
                return resp(environ, start_response)
            tokens = gateway.wait(pending)
            lat_ms = (pending.t_done - pending.req.t_submitted) * 1e3
            resp = _json({"tokens": tokens, "latency_ms": lat_ms})
        except HTTPException as e:
            resp = e
        return resp(environ, start_response)

    app.gateway = gateway
    return app
