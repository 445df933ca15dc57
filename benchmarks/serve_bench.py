#!/usr/bin/env python3
"""HTTP serving throughput + speculative-decode workload bench.

Five campaigns, each printing one JSON line:

- ``serve``: boot ``examples/serve_llama.py``'s app in-process on a
  synthetic-weight model (``--preset`` / ``--quant``), fire N requests
  at C concurrency from real HTTP clients, report warm tokens/sec and
  latency percentiles — the 7B companion of r4's 1.2B ``serving_http``
  block (VERDICT r5 item 2).
- ``spec``: measure prompt-lookup speculative decoding on the workload
  it was designed for — continuation of REPETITIVE text (code/docs
  where the continuation echoes the prompt) — against plain fused
  decode, reporting acceptance and net speedup (VERDICT r5 item 8).
  The model is trained briefly on a tiny repetitive corpus so greedy
  continuations actually repeat (random weights accept nothing —
  that's r4's measured worst case, not the win case).
- ``decode``: the int4 decode-path A/B behind the unpack-once fix —
  per-token-loop vs fused-with-hoist vs fused-re-unpack (the pre-fix
  trace, restored via ``set_unpack_once(False)``) on one host, ms/tok
  each. Feeds ``SERVE_r01.json`` ``decode_int4``.
- ``storm``: the many-tenant serving storm — a mixed-length,
  mixed-budget request schedule from T victim tenants plus one
  flooding tenant, replayed against three same-host arms
  (continuous batching + admission control, continuous without
  admission, and serve_llama's static batcher), reporting per-tenant
  p50/p95, aggregate USEFUL tokens/sec (tokens a request asked for —
  the static arm decodes its server-fixed budget regardless), batch
  occupancy, queue depth, and shed counts. Feeds ``SERVE_r01.json``.
- ``prefix_storm``: the r13 prefix-heavy storm — 80% of requests open
  with one long shared system prompt, replayed against the block-paged
  engine (CoW prefix sharing) and the r12 contiguous engine on the
  same host/weights, plus a ServingFleet chaos pass that hard-kills a
  replica mid-storm (every request must migrate and finish exactly).
  Feeds ``SERVE_r02.json``.
- ``disagg_storm``: the r17 disaggregated-fleet storm — interactive
  shared-prefix traffic mixed with long-prefill batch/best_effort
  traffic (some speculative), replayed against the r13 symmetric
  fleet and a prefill/decode split fleet with the fleet-wide
  GlobalBlockStore, same host/weights. Every request is checked
  token-exact against solo fused decode; both arms then take
  two-replica chaos kills plus a post-kill prefix probe. Feeds
  ``SERVE_r03.json``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def serve_campaign(preset: str, quant: str | None, requests_n: int,
                   concurrency: int, max_new: int) -> dict:
    import jax
    import numpy as np
    from werkzeug.serving import make_server

    from examples.serve_llama import make_app
    from kubeflow_rm_tpu.models import LlamaConfig, init_params

    cfg = getattr(LlamaConfig, preset)(param_dtype=jax.numpy.bfloat16) \
        if jax.devices()[0].platform == "tpu" \
        else getattr(LlamaConfig, preset)()
    if quant:
        from kubeflow_rm_tpu.models.quantize import init_params_quantized
        params = init_params_quantized(cfg, jax.random.key(0),
                                       bits=4 if quant == "int4" else 8)
    else:
        params = init_params(cfg, jax.random.key(0))

    app = make_app(cfg, params, max_new_tokens=max_new, window_ms=8,
                   max_batch=16)
    httpd = make_server("127.0.0.1", 0, app, threaded=True)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_port}/generate"

    rng = np.random.default_rng(0)
    # one prompt-length bucket (96-127) like the r4 block
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(96, 128))).tolist()
               for _ in range(requests_n)]

    import urllib.request

    def call(p):
        t0 = time.perf_counter()
        req = urllib.request.Request(
            url, data=json.dumps({"prompt": p}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.loads(urllib.request.urlopen(req, timeout=600).read())
        assert len(body["tokens"]) == len(p) + max_new
        return time.perf_counter() - t0

    # warm: one concurrency-wide wave so the coalesced batch shapes
    # (not just batch-1) compile BEFORE the timed region
    warm_ts = [threading.Thread(target=call, args=(p,))
               for p in prompts[:concurrency]]
    for t in warm_ts:
        t.start()
    for t in warm_ts:
        t.join()
    call(prompts[0])  # and the solo shape

    lat: list[float] = []
    lock = threading.Lock()
    idx = {"i": 1}

    def worker():
        while True:
            with lock:
                i = idx["i"]
                if i >= len(prompts):
                    return
                idx["i"] = i + 1
            d = call(prompts[i])
            with lock:
                lat.append(d)

    t0 = time.perf_counter()
    ts = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    lat.sort()
    n = len(lat)
    return {
        "metric": "serving_http",
        "model": f"llama-{preset}" + (f" {quant}" if quant else " bf16"),
        "requests": n,
        "concurrency": concurrency,
        "new_tokens_per_req": max_new,
        "warm_requests_per_s": round(n / wall, 2),
        "warm_gen_tokens_per_s": round(n * max_new / wall, 1),
        "latency_p50_s": round(lat[n // 2], 2),
        "latency_p95_s": round(lat[max(0, int(n * 0.95) - 1)], 2),
        "batches": app.batcher.batches_run,
    }


def spec_campaign(preset: str, train_steps: int, max_new: int) -> dict:
    """Train a small model on repetitive text, then decode
    continuations of its own training prefixes — the prompt-lookup
    decoder's intended workload — vs plain fused decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_rm_tpu.models import LlamaConfig
    from kubeflow_rm_tpu.models.generate import (
        generate_fused, generate_speculative_fused,
    )
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_rm_tpu.training.train import (
        TrainConfig, init_train_state, make_train_step, shard_batch,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = getattr(LlamaConfig, preset)(
        **({"param_dtype": jnp.bfloat16} if on_tpu else {}))
    mesh = make_mesh(MeshConfig(), jax.devices()[:1])
    tc = TrainConfig(model=cfg)
    state = init_train_state(tc, jax.random.key(0))
    step = make_train_step(tc, mesh, state)

    # a tiny repetitive corpus: short token phrases repeated many times
    rng = np.random.default_rng(0)
    phrases = [rng.integers(2, min(cfg.vocab_size, 200), size=8).tolist()
               for _ in range(4)]
    seq_len = min(cfg.max_seq_len, 256)
    doc = []
    while len(doc) < 8 * seq_len:
        doc += phrases[rng.integers(0, len(phrases))]
    toks = np.array(doc[:8 * seq_len], np.int32).reshape(8, seq_len)
    batch = shard_batch(
        {"tokens": toks, "labels": np.roll(toks, -1, 1)}, mesh)
    for _ in range(train_steps):
        state, metrics = step(state, batch)
    loss = float(jax.device_get(metrics["loss"]))

    # prompt = a training row prefix; greedy continuation repeats it
    prompt = jnp.asarray(toks[:1, :96])

    def timed(fn):
        out = fn()
        jax.device_get(np.asarray(out)[:, -1])
        t0 = time.perf_counter()
        out = fn()
        jax.device_get(np.asarray(out)[:, -1])
        return np.asarray(out), time.perf_counter() - t0

    plain, t_plain = timed(lambda: generate_fused(
        state.params, cfg, prompt, max_new_tokens=max_new))
    spec, t_spec = timed(lambda: generate_speculative_fused(
        state.params, cfg, prompt, max_new_tokens=max_new, lookup_n=3))
    match = bool((plain[0, :spec.shape[1]] == spec[0]).all()) \
        or bool((spec[0, :plain.shape[1]] == plain[0]).all())
    return {
        "metric": "speculative_repetitive_workload",
        "model": f"llama-{preset}",
        "train_steps": train_steps,
        "final_loss": round(loss, 3),
        "new_tokens": max_new,
        "plain_ms_per_token": round(1e3 * t_plain / max_new, 2),
        "spec_ms_per_token": round(1e3 * t_spec / max_new, 2),
        "net_speedup": round(t_plain / t_spec, 2),
        "outputs_match": match,
    }


def _device_tag() -> str:
    import os

    import jax
    plat = jax.devices()[0].platform
    if plat == "cpu":
        return f"cpu-{os.cpu_count()}core"
    return f"{plat}x{len(jax.devices())}"


def decode_campaign(preset: str, batch: int, prompt_len: int,
                    max_new: int, overrides: dict) -> dict:
    """Int4 decode-path A/B: per-token loop vs fused-with-hoist vs
    fused re-unpacking inside the scan (the pre-fix trace, restored
    via ``set_unpack_once(False)``). All three arms decode the SAME
    prompts greedily on the same host; the fused arms must also agree
    token-for-token with the loop (exactness is part of the claim)."""
    import jax
    import numpy as np

    from kubeflow_rm_tpu.models import LlamaConfig, generate_fused
    from kubeflow_rm_tpu.models.generate import generate, set_unpack_once
    from kubeflow_rm_tpu.models.quantize import init_params_quantized

    cfg = getattr(LlamaConfig, preset)(**overrides)
    params = init_params_quantized(cfg, jax.random.key(0), bits=4)
    rng = np.random.default_rng(0)
    ids = jax.numpy.asarray(
        rng.integers(1, cfg.vocab_size, size=(batch, prompt_len)),
        jax.numpy.int32)
    total = prompt_len + max_new

    def timed(fn, reps: int = 3):
        out = fn()                       # compile + warm
        jax.device_get(np.asarray(out)[:, -1])
        ts = []
        for _ in range(reps):            # median: CPU hosts are noisy
            t0 = time.perf_counter()
            out = fn()
            jax.device_get(np.asarray(out)[:, -1])
            ts.append(time.perf_counter() - t0)
        return np.asarray(out), sorted(ts)[len(ts) // 2]

    loop, t_loop = timed(lambda: generate(
        params, cfg, ids, max_new_tokens=max_new, max_len=total))
    set_unpack_once(True)
    fused, t_fused = timed(lambda: generate_fused(
        params, cfg, ids, max_new_tokens=max_new, max_len=total))
    set_unpack_once(False)               # pre-fix arm: unpack per step
    refused, t_reunpack = timed(lambda: generate_fused(
        params, cfg, ids, max_new_tokens=max_new, max_len=total))
    set_unpack_once(True)
    return {
        "metric": "decode_int4",
        "model": f"llama-{preset} int4"
                 + (f" {overrides}" if overrides else ""),
        "device": _device_tag(),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": max_new,
        "loop_ms_per_tok": round(1e3 * t_loop / max_new, 2),
        "fused_ms_per_tok": round(1e3 * t_fused / max_new, 2),
        "fused_reunpack_ms_per_tok": round(1e3 * t_reunpack / max_new, 2),
        "fused_le_loop": bool(t_fused <= t_loop),
        "outputs_match": bool((loop == fused).all()
                              and (loop == refused).all()),
    }


def storm_campaign(preset: str, quant: str | None, tenants: int,
                   reqs_per_tenant: int, flood_threads: int,
                   flood_reqs: int, slots: int, slot_len: int,
                   slo_ms: float, qps: float, burst: int,
                   overrides: dict | None = None) -> dict:
    """Many-tenant serving storm over three same-host arms sharing one
    set of weights:

    - ``continuous_admission``: ContinuousBatchingEngine behind
      ServingGateway with per-tenant rate/token buckets + SLO shedding.
    - ``continuous_no_admission``: same engine, ``admission=False``
      (only the queue cap survives) — the noisy-neighbor baseline.
    - ``static``: serve_llama's window-coalescing fixed-shape batcher,
      which decodes its server-fixed budget for every request.

    T victim tenants each send a mixed-length, mixed-budget schedule
    at a polite rate; one flood tenant hammers from ``flood_threads``
    parallel connections. Useful tokens = the ``max_new`` each request
    ASKED for (the static arm decodes its fixed budget regardless, so
    its extra tokens are waste, not throughput)."""
    import logging
    import urllib.error
    import urllib.request

    import jax
    import numpy as np
    from werkzeug.serving import make_server

    # one log line per request x hundreds of storm requests = noise
    logging.getLogger("werkzeug").setLevel(logging.ERROR)

    from examples.serve_llama import make_app
    from kubeflow_rm_tpu.controlplane.webapps.serving import (
        ServingGateway, TenantPolicy, make_serving_app,
    )
    from kubeflow_rm_tpu.models import (
        ContinuousBatchingEngine, LlamaConfig, init_params,
    )

    cfg = getattr(LlamaConfig, preset)(**(overrides or {}))
    if quant:
        from kubeflow_rm_tpu.models.quantize import init_params_quantized
        params = init_params_quantized(cfg, jax.random.key(0),
                                       bits=4 if quant == "int4" else 8)
    else:
        params = init_params(cfg, jax.random.key(0))

    # Long-tail budgets: the static server must fix max_new at the tail
    # (32) and decode it for EVERY request; the engine retires each
    # request at its own ask.  avg ask ~= 14.7 vs 32 decoded is the
    # over-decode waste the continuous arm gets back.
    budgets = (4, 8, 32)
    max_budget = max(budgets)
    rng = np.random.default_rng(7)
    # (tenant, prompt, max_new, gap_s) — victims pace themselves,
    # the flood tenant does not
    schedule: dict[str, list] = {}
    for t in range(tenants):
        name = f"tenant-{t}"
        schedule[name] = [
            (rng.integers(1, cfg.vocab_size,
                          size=int(rng.integers(8, 49))).tolist(),
             int(budgets[rng.integers(0, len(budgets))]),
             0.02)
            for _ in range(reqs_per_tenant)]
    # the flood is mixed-length/mixed-budget too — a noisy tenant is
    # ordinary traffic at extraordinary volume
    flood_work = [
        (rng.integers(1, cfg.vocab_size,
                      size=int(rng.integers(8, 49))).tolist(),
         int(budgets[rng.integers(0, len(budgets))]))
        for _ in range(flood_reqs)]

    def run_storm(url: str) -> tuple[list[dict], float]:
        results: list[dict] = []
        lock = threading.Lock()

        def call(tenant, prompt, m):
            body = {"prompt": prompt, "tenant": tenant,
                    "max_new_tokens": m}
            t0 = time.perf_counter()
            req = urllib.request.Request(
                url, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json",
                         "X-Tenant": tenant})
            try:
                resp = json.loads(
                    urllib.request.urlopen(req, timeout=600).read())
                ok, reason = True, None
                # gateway arms return the continuation, the static arm
                # prompt+continuation — both non-empty on success
                assert resp["tokens"], resp
            except urllib.error.HTTPError as e:
                ok = False
                try:
                    reason = json.loads(e.read()).get("reason", str(e.code))
                except Exception:
                    reason = str(e.code)
            lat = time.perf_counter() - t0
            with lock:
                results.append({"tenant": tenant, "ok": ok,
                                "reason": reason, "useful": m if ok else 0,
                                "lat_ms": lat * 1e3})

        def victim(name):
            for prompt, m, gap in schedule[name]:
                call(name, prompt, m)
                time.sleep(gap)

        def flooder(i):
            for j in range(i, len(flood_work), flood_threads):
                call("flood", *flood_work[j])

        ts = ([threading.Thread(target=victim, args=(n,))
               for n in schedule]
              + [threading.Thread(target=flooder, args=(i,))
                 for i in range(flood_threads)])
        t0 = time.perf_counter()
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        return results, time.perf_counter() - t0

    def summarize(results, wall, extra) -> dict:
        def pct(v, q):
            return round(v[min(len(v) - 1, int(q * (len(v) - 1)))], 1)

        per_tenant = {}
        for name in sorted({r["tenant"] for r in results}):
            lats = sorted(r["lat_ms"] for r in results
                          if r["tenant"] == name and r["ok"])
            per_tenant[name] = {
                "ok": len(lats),
                "shed": sum(1 for r in results
                            if r["tenant"] == name and not r["ok"]),
                "p50_ms": pct(lats, 0.50) if lats else None,
                "p95_ms": pct(lats, 0.95) if lats else None,
            }
        victim_p95 = [v["p95_ms"] for k, v in per_tenant.items()
                      if k != "flood" and v["p95_ms"] is not None]
        return {
            "wall_s": round(wall, 2),
            "ok": sum(1 for r in results if r["ok"]),
            "shed": sum(1 for r in results if not r["ok"]),
            "useful_tokens": sum(r["useful"] for r in results),
            "useful_tok_per_s": round(
                sum(r["useful"] for r in results) / wall, 1),
            "victim_p95_ms_worst": max(victim_p95) if victim_p95 else None,
            "per_tenant": per_tenant,
            **extra,
        }

    def continuous_arm(admission: bool) -> dict:
        engine = ContinuousBatchingEngine(params, cfg, slots=slots,
                                          slot_len=slot_len)
        gw = ServingGateway(
            engine,
            default_policy=TenantPolicy(qps=qps, burst=burst,
                                        tokens_per_s=qps * 16,
                                        token_burst=burst * 16,
                                        slo_p95_ms=slo_ms),
            max_queue=64, admission=admission)
        app = make_serving_app(gw, cfg)
        httpd = make_server("127.0.0.1", 0, app, threaded=True)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{httpd.server_port}/generate"
        # warm every prefill bucket (8/16/32/64) + decode/install
        for n in (8, 12, 32, 48):
            warm = urllib.request.Request(
                url, data=json.dumps(
                    {"prompt": list(range(1, n + 1)), "tenant": "warm",
                     "max_new_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(warm, timeout=600).read()
        results, wall = run_storm(url)
        snap = gw.snapshot()
        httpd.shutdown()
        gw.close()
        return summarize(results, wall, {
            "admission": admission,
            "batch_occupancy": round(snap["batch_occupancy"], 3),
            "decode_steps": snap["decode_steps"],
            "shed_reasons": snap["shed"],
        })

    def static_arm() -> dict:
        app = make_app(cfg, params, max_new_tokens=max_budget,
                       window_ms=8, max_batch=slots)
        httpd = make_server("127.0.0.1", 0, app, threaded=True)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        url = f"http://127.0.0.1:{httpd.server_port}/generate"
        # warm the static batcher's (B, T) compile grid: waves at
        # several concurrencies so the storm doesn't pay XLA compiles
        def warm_one(n):
            urllib.request.urlopen(urllib.request.Request(
                url, data=json.dumps(
                    {"prompt": list(range(1, n + 1))}).encode(),
                headers={"Content-Type": "application/json"}),
                timeout=600).read()

        for wave in ((48,), (8, 40), (8, 16, 24, 48),
                     (8, 16, 24, 32, 40, 48, 12, 20)):
            warm_ts = [threading.Thread(target=warm_one, args=(n,))
                       for n in wave]
            for t in warm_ts:
                t.start()
            for t in warm_ts:
                t.join()
        results, wall = run_storm(url)
        batches = app.batcher.batches_run
        httpd.shutdown()
        app.batcher.close()
        return summarize(results, wall, {
            "fixed_max_new": max_budget, "batches": batches})

    return {
        "metric": "serving_storm",
        "model": f"llama-{preset}" + (f" {quant}" if quant else " bf16")
                 + (f" {overrides}" if overrides else ""),
        "device": _device_tag(),
        "workload": {
            "victim_tenants": tenants,
            "reqs_per_tenant": reqs_per_tenant,
            "flood_threads": flood_threads,
            "flood_reqs": flood_reqs,
            "budgets": list(budgets),
            "slots": slots, "slot_len": slot_len,
            "slo_p95_ms": slo_ms,
        },
        "arms": {
            "continuous_admission": continuous_arm(True),
            "continuous_no_admission": continuous_arm(False),
            "static": static_arm(),
        },
    }


def prefix_storm_campaign(preset: str, quant: str | None, tenants: int,
                          reqs_per_tenant: int, flood_threads: int,
                          flood_reqs: int, slots: int, slot_len: int,
                          block_size: int, shared_len: int,
                          chaos_replicas: int,
                          overrides: dict | None = None) -> dict:
    """The r13 prefix-heavy storm: 80% of traffic opens with one long
    shared system prompt, replayed against two same-host arms sharing
    one set of weights:

    - ``paged``: the block-paged engine (``paged=True``) — the shared
      prefix is content-addressed in the block pool, so repeat prompts
      adopt the cached blocks and prefill only their short tail.
    - ``contiguous``: the r12 contiguous-slot engine (``paged=False``)
      on the SAME traffic — every request re-prefills the full prompt.

    Victims submit as ``interactive``, the flood as ``best_effort``,
    so the in-engine weighted queues (not gateway-side shedding) set
    the victim p95. Both arms run ``admission=False``: nothing sheds,
    every request completes, and useful tok/s compares the engines —
    not the admission policy. Each arm also answers one known prompt
    at the end and checks it bit-identical to solo ``generate_fused``.

    A third ``chaos`` pass runs the paged engine as a
    ``ServingFleet`` of N replicas and hard-kills the affinity owner
    mid-storm: every in-flight request must migrate and finish with
    exactly the tokens an uninterrupted run produces — zero failures.
    """
    import logging
    import urllib.error
    import urllib.request

    import jax
    import jax.numpy as jnp
    import numpy as np
    from werkzeug.serving import make_server

    logging.getLogger("werkzeug").setLevel(logging.ERROR)

    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import (
        ServingGateway, make_serving_app,
    )
    from kubeflow_rm_tpu.models import (
        ContinuousBatchingEngine, LlamaConfig, init_params,
    )
    from kubeflow_rm_tpu.models.generate import generate_fused

    cfg = getattr(LlamaConfig, preset)(**(overrides or {}))
    if quant:
        from kubeflow_rm_tpu.models.quantize import init_params_quantized
        params = init_params_quantized(cfg, jax.random.key(0),
                                       bits=4 if quant == "int4" else 8)
    else:
        params = init_params(cfg, jax.random.key(0))

    budgets = (4, 8)
    rng = np.random.default_rng(13)
    # the one system prompt 80% of traffic opens with; tails of 4-8
    # keep every shared request inside a single small suffix bucket
    shared_sys = rng.integers(1, cfg.vocab_size,
                              size=shared_len).tolist()

    def one_request():
        if rng.random() < 0.8:
            tail = rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(4, 9))).tolist()
            return shared_sys + tail, True
        p = rng.integers(1, cfg.vocab_size,
                         size=int(rng.integers(shared_len,
                                               shared_len + 9))).tolist()
        return p, False

    schedule: dict[str, list] = {}
    shared_n = total_n = 0
    for t in range(tenants):
        work = []
        for _ in range(reqs_per_tenant):
            p, is_shared = one_request()
            shared_n += is_shared
            total_n += 1
            work.append((p, int(budgets[rng.integers(0, len(budgets))]),
                         0.02))
        schedule[f"tenant-{t}"] = work
    flood_work = []
    for _ in range(flood_reqs):
        p, is_shared = one_request()
        shared_n += is_shared
        total_n += 1
        flood_work.append(
            (p, int(budgets[rng.integers(0, len(budgets))])))

    def run_storm(url: str) -> tuple[list[dict], float]:
        results: list[dict] = []
        lock = threading.Lock()

        def call(tenant, prompt, m, slo_class):
            body = {"prompt": prompt, "tenant": tenant,
                    "max_new_tokens": m, "slo_class": slo_class}
            t0 = time.perf_counter()
            req = urllib.request.Request(
                url, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            try:
                resp = json.loads(
                    urllib.request.urlopen(req, timeout=600).read())
                ok = bool(resp["tokens"])
            except urllib.error.HTTPError:
                ok = False
            lat = time.perf_counter() - t0
            with lock:
                results.append({"tenant": tenant, "ok": ok,
                                "useful": m if ok else 0,
                                "lat_ms": lat * 1e3})

        def victim(name):
            for prompt, m, gap in schedule[name]:
                call(name, prompt, m, "interactive")
                time.sleep(gap)

        def flooder(i):
            for j in range(i, len(flood_work), flood_threads):
                call("flood", *flood_work[j], "best_effort")

        ts = ([threading.Thread(target=victim, args=(n,))
               for n in schedule]
              + [threading.Thread(target=flooder, args=(i,))
                 for i in range(flood_threads)])
        t0 = time.perf_counter()
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        return results, time.perf_counter() - t0

    def summarize(results, wall) -> dict:
        def pct(v, q):
            return round(v[min(len(v) - 1, int(q * (len(v) - 1)))], 1)

        per_tenant = {}
        for name in sorted({r["tenant"] for r in results}):
            lats = sorted(r["lat_ms"] for r in results
                          if r["tenant"] == name and r["ok"])
            per_tenant[name] = {
                "ok": len(lats),
                "p50_ms": pct(lats, 0.50) if lats else None,
                "p95_ms": pct(lats, 0.95) if lats else None,
            }
        victim_p95 = [v["p95_ms"] for k, v in per_tenant.items()
                      if k != "flood" and v["p95_ms"] is not None]
        return {
            "wall_s": round(wall, 2),
            "ok": sum(1 for r in results if r["ok"]),
            "failed": sum(1 for r in results if not r["ok"]),
            "useful_tokens": sum(r["useful"] for r in results),
            "useful_tok_per_s": round(
                sum(r["useful"] for r in results) / wall, 1),
            "victim_p95_ms_worst": max(victim_p95) if victim_p95
            else None,
            "per_tenant": per_tenant,
        }

    def solo(prompt, budget):
        ref = generate_fused(params, cfg,
                             jnp.asarray([prompt], jnp.int32),
                             max_new_tokens=budget, max_len=slot_len)
        return np.asarray(ref)[0, len(prompt):].tolist()

    check_prompt = shared_sys + [1, 2, 3, 4]
    check_want = solo(check_prompt, 8)

    def engine_arm(paged: bool) -> dict:
        engine = ContinuousBatchingEngine(params, cfg, slots=slots,
                                          slot_len=slot_len, paged=paged,
                                          block_size=block_size)
        gw = ServingGateway(engine, max_queue=100_000, admission=False)
        app = make_serving_app(gw, cfg)
        httpd = make_server("127.0.0.1", 0, app, threaded=True)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{httpd.server_port}/generate"

        def post(prompt, m):
            req = urllib.request.Request(
                url, data=json.dumps(
                    {"prompt": prompt, "tenant": "warm",
                     "max_new_tokens": m}).encode(),
                headers={"Content-Type": "application/json"})
            return json.loads(
                urllib.request.urlopen(req, timeout=600).read())

        # warm BOTH prefill paths before the timed region: a full-miss
        # prompt (big bucket, registers the shared chain) and a
        # shared-prefix sibling (small suffix bucket on the paged arm)
        post(list(shared_sys) + [9, 9, 9, 9], 4)
        post(list(shared_sys) + [9, 9, 9, 8], 4)          # 4-token tail
        post(list(shared_sys) + [9, 8, 7, 6, 5, 4, 3, 2], 4)  # 8-token
        post([1 + i % (cfg.vocab_size - 2)
              for i in range(shared_len + 3)], 4)

        results, wall = run_storm(url)
        got = post(check_prompt, 8)["tokens"]
        st = engine.stats()
        snap = gw.snapshot()
        httpd.shutdown()
        gw.close()
        out = summarize(results, wall)
        out.update({
            "paged": paged,
            "sample_exact": got == check_want,
            "batch_occupancy": round(snap["batch_occupancy"], 3),
            "decode_steps": snap["decode_steps"],
        })
        if paged:
            out.update({
                "prefix_hit_ratio": st["prefix_hit_ratio"],
                "prefix_hit_tokens": st["prefix_hit_tokens"],
                "cow_forks": st["cow_forks"],
                "block_evictions": st["evictions"],
            })
        return out

    def chaos_arm() -> dict:
        fleet = ServingFleet({
            f"r{i}": ServingGateway(
                ContinuousBatchingEngine(params, cfg, slots=slots,
                                         slot_len=slot_len,
                                         block_size=block_size),
                max_queue=100_000, admission=False)
            for i in range(chaos_replicas)})
        try:
            prompts = [shared_sys + [7, 7, 7, i] for i in range(6)] \
                + [[3 + i % (cfg.vocab_size - 4)
                    for i in range(shared_len + 4)], shared_sys[::-1]]
            want = {i: solo(p, 12) for i, p in enumerate(prompts)}
            jobs = [(i % len(prompts)) for i in range(3 * len(prompts))]
            results: list = [None] * len(jobs)

            def go(j):
                results[j] = fleet.submit_and_wait(
                    "chaos", list(prompts[jobs[j]]), max_new_tokens=12,
                    slo_class="interactive")

            victim = fleet.route(prompts[0])
            ts = [threading.Thread(target=go, args=(j,))
                  for j in range(len(jobs))]
            t0 = time.perf_counter()
            for th in ts:
                th.start()
            # hard-kill the affinity owner the moment it holds work
            gw = fleet.gateways[victim]
            deadline = time.monotonic() + 60
            while (not gw.engine.active_slots
                   and time.monotonic() < deadline):
                time.sleep(0.001)
            fleet.kill(victim)
            for th in ts:
                th.join()
            wall = time.perf_counter() - t0
            failed = sum(1 for r in results
                         if r is None or r[0] is None)
            exact = sum(1 for j, r in enumerate(results)
                        if r is not None and r[0] == want[jobs[j]])
            return {
                "replicas": chaos_replicas,
                "killed": victim,
                "requests": len(jobs),
                "failed": failed,
                "exact": exact,
                "all_exact": exact == len(jobs),
                "migrations": fleet.migrations,
                "wall_s": round(wall, 2),
            }
        finally:
            fleet.close()

    paged = engine_arm(True)
    contiguous = engine_arm(False)
    chaos = chaos_arm()
    speedup = round(paged["useful_tok_per_s"]
                    / max(1e-9, contiguous["useful_tok_per_s"]), 2)
    return {
        "metric": "serving_prefix_storm",
        "model": f"llama-{preset}" + (f" {quant}" if quant else " bf16")
                 + (f" {overrides}" if overrides else ""),
        "device": _device_tag(),
        "workload": {
            "victim_tenants": tenants,
            "reqs_per_tenant": reqs_per_tenant,
            "flood_threads": flood_threads,
            "flood_reqs": flood_reqs,
            "shared_prefix_len": shared_len,
            "shared_fraction": round(shared_n / max(1, total_n), 3),
            "budgets": list(budgets),
            "slots": slots, "slot_len": slot_len,
            "block_size": block_size,
        },
        "arms": {"paged": paged, "contiguous": contiguous},
        "paged_speedup": speedup,
        "paged_ge_2x": speedup >= 2.0,
        "chaos": chaos,
    }


def disagg_storm_campaign(preset: str, quant: str | None, tenants: int,
                          reqs_per_tenant: int, flood_threads: int,
                          flood_reqs: int, slots: int, slot_len: int,
                          block_size: int, shared_len: int,
                          replicas: int, store_mb: int,
                          long_len: int | None = None,
                          num_blocks: int | None = None,
                          overrides: dict | None = None) -> dict:
    """The r17 disaggregated-serving storm: interactive shared-prefix
    victims plus long-prefill batch/best_effort flooders (every few
    flood requests decode speculatively), replayed against two
    same-host fleet arms sharing one set of weights:

    - ``symmetric``: the r13 fleet — N identical replicas, prefix-
      affinity routing, per-replica prefix caches, no shared state.
    - ``disagg``: 1 prefill replica + N-1 decode replicas. Long
      prompts prefill on the prefill tier into block chains published
      to the fleet-wide GlobalBlockStore; decode replicas are picked
      by queue depth and adopt chains by hash, and hot ref-0 chains
      promote back to the store on local eviction.

    EVERY request — storm, chaos wave, and probe — is checked
    token-exact against solo ``generate_fused`` on the same weights;
    the throughput/latency claims are conditional on bit-identical
    output. After the timed storm each arm takes two hard kills while
    a chaos wave is in flight: the prefill replica (the shared-prefix
    affinity owner on the symmetric arm) and the decode replica
    holding the most shared-prefix blocks. Every in-flight request
    must migrate and finish exactly. A post-kill probe (shared prefix
    + fresh tail) then measures where the prefix went: the symmetric
    arm buried it with the killed owner, the disagg arm re-adopts it
    from the store."""
    import logging

    import jax
    import jax.numpy as jnp
    import numpy as np

    logging.getLogger("werkzeug").setLevel(logging.ERROR)

    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import ServingGateway
    from kubeflow_rm_tpu.models import (
        ContinuousBatchingEngine, LlamaConfig, init_params,
    )
    from kubeflow_rm_tpu.models.generate import generate_fused

    if replicas < 3:
        raise ValueError("disagg_storm kills two replicas mid-wave; "
                         "--replicas must be >= 3")
    cfg = getattr(LlamaConfig, preset)(**(overrides or {}))
    if quant:
        from kubeflow_rm_tpu.models.quantize import init_params_quantized
        params = init_params_quantized(cfg, jax.random.key(0),
                                       bits=4 if quant == "int4" else 8)
    else:
        params = init_params(cfg, jax.random.key(0))

    if long_len is None:
        long_len = min(2 * shared_len, slot_len - 24)
    rng = np.random.default_rng(17)
    vocab = cfg.vocab_size
    shared_sys = rng.integers(1, vocab, size=shared_len).tolist()

    # finite prompt pools so EVERY request has a precomputed greedy
    # reference — exactness is asserted for the whole storm, not for
    # one sample at the end
    victim_pool = [shared_sys
                   + rng.integers(1, vocab, size=4).tolist()
                   for _ in range(8)]
    long_pool = [rng.integers(1, vocab, size=long_len).tolist()
                 for _ in range(8)]
    chaos_pool = [rng.integers(1, vocab, size=shared_len + 6).tolist()
                  for _ in range(4)]
    probe = shared_sys + rng.integers(1, vocab, size=5).tolist()

    budgets = (4, 8)
    victim_jobs: dict[str, list] = {}
    for t in range(tenants):
        victim_jobs[f"tenant-{t}"] = [
            (victim_pool[int(rng.integers(0, len(victim_pool)))],
             int(budgets[int(rng.integers(0, len(budgets)))]), 0.02)
            for _ in range(reqs_per_tenant)]
    # long-prefill flood: batch/best_effort, every 4th speculative
    flood_jobs = [
        (long_pool[int(rng.integers(0, len(long_pool)))], 8,
         "best_effort" if j % 2 else "batch", j % 4 == 0)
        for j in range(flood_reqs)]

    def solo(prompt, budget):
        ref = generate_fused(params, cfg,
                             jnp.asarray([prompt], jnp.int32),
                             max_new_tokens=budget, max_len=slot_len)
        return np.asarray(ref)[0, len(prompt):].tolist()

    # greedy decode is prefix-stable, so one reference at the largest
    # budget a prompt is ever asked for covers every smaller ask
    want: dict[tuple, list] = {}

    def want_for(prompt, budget):
        key = tuple(prompt)
        if key not in want or len(want[key]) < budget:
            want[key] = solo(prompt, budget)
        return want[key][:budget]

    for p in victim_pool:
        want_for(p, max(budgets))
    for p in long_pool:
        want_for(p, 8)
    for p in chaos_pool:
        want_for(p, 12)
    want_for(probe, 8)

    eng_kw: dict = dict(slots=slots, slot_len=slot_len, paged=True,
                        block_size=block_size)
    if num_blocks:
        eng_kw["num_blocks"] = num_blocks

    def run_arm(disagg: bool) -> dict:
        if disagg:
            names = (["prefill-0"]
                     + [f"decode-{i}" for i in range(replicas - 1)])
            roles = {n: ("prefill" if n.startswith("prefill")
                         else "decode") for n in names}
        else:
            names = [f"r{i}" for i in range(replicas)]
            roles = None
        gws = {n: ServingGateway(
            ContinuousBatchingEngine(params, cfg, **eng_kw),
            max_queue=100_000, admission=False) for n in names}
        fleet = (ServingFleet(gws, roles=roles,
                              store_bytes=store_mb << 20)
                 if roles else ServingFleet(gws))
        try:
            results: list[dict] = []
            lock = threading.Lock()

            def call(tenant, prompt, m, slo, spec=False):
                t0 = time.perf_counter()
                toks, _info = fleet.submit_and_wait(
                    tenant, list(prompt), max_new_tokens=m,
                    slo_class=slo, speculative=spec)
                lat = (time.perf_counter() - t0) * 1e3
                ok = toks is not None
                with lock:
                    results.append({
                        "tenant": tenant, "ok": ok,
                        "exact": ok and toks == want_for(prompt, m),
                        "useful": m if ok else 0, "lat_ms": lat,
                        "interactive": slo == "interactive",
                        "speculative": spec})

            # warm the compile buckets (and each arm's prefix state)
            # before the timed region — including the speculative
            # path, whose first compile would otherwise land inside
            # whichever arm runs first
            call("warm", shared_sys + [9, 9, 9, 9], 4, "interactive")
            call("warm", long_pool[0], 4, "batch")
            call("warm", long_pool[1], 4, "best_effort", True)
            with lock:
                results.clear()

            def victim(name):
                for prompt, m, gap in victim_jobs[name]:
                    call(name, prompt, m, "interactive")
                    time.sleep(gap)

            def flooder(i):
                for j in range(i, len(flood_jobs), flood_threads):
                    p, m, slo, spec = flood_jobs[j]
                    call("flood", p, m, slo, spec)

            ts = ([threading.Thread(target=victim, args=(n,))
                   for n in victim_jobs]
                  + [threading.Thread(target=flooder, args=(i,))
                     for i in range(flood_threads)])
            t0 = time.perf_counter()
            for th in ts:
                th.start()
            for th in ts:
                th.join()
            wall = time.perf_counter() - t0

            def pct(v, q):
                return round(
                    v[min(len(v) - 1, int(q * (len(v) - 1)))], 1)

            inter = sorted(r["lat_ms"] for r in results
                           if r["interactive"] and r["ok"])
            per_tenant_p95 = []
            for name in victim_jobs:
                lats = sorted(r["lat_ms"] for r in results
                              if r["tenant"] == name and r["ok"])
                if lats:
                    per_tenant_p95.append(pct(lats, 0.95))
            arm = {
                "wall_s": round(wall, 2),
                "ok": sum(1 for r in results if r["ok"]),
                "failed": sum(1 for r in results if not r["ok"]),
                "exact": sum(1 for r in results if r["exact"]),
                "all_exact": all(r["exact"] for r in results),
                "useful_tokens": sum(r["useful"] for r in results),
                "useful_tok_per_s": round(
                    sum(r["useful"] for r in results) / wall, 1),
                "interactive_p50_ms": pct(inter, 0.50) if inter
                else None,
                "interactive_p95_ms": pct(inter, 0.95) if inter
                else None,
                "victim_p95_ms_worst": max(per_tenant_p95)
                if per_tenant_p95 else None,
                "speculative_requests": sum(
                    1 for r in results if r["speculative"]),
            }

            # --- chaos: two kills while a wave is in flight ---------
            if disagg:
                kill_first = "prefill-0"
                decs = [n for n in names if roles[n] == "decode"]
                kill_second = max(
                    decs, key=lambda n: gws[n].chain_coverage(probe))
            else:
                kill_first = fleet.route(list(probe))
                rest = [n for n in names if n != kill_first]
                kill_second = max(
                    rest, key=lambda n: gws[n].chain_coverage(probe))
            chaos_jobs = [chaos_pool[i % len(chaos_pool)]
                          for i in range(2 * len(chaos_pool))]
            chaos_res: list = [None] * len(chaos_jobs)

            def go(j):
                chaos_res[j] = fleet.submit_and_wait(
                    "chaos", list(chaos_jobs[j]), max_new_tokens=12,
                    slo_class="batch")

            cts = [threading.Thread(target=go, args=(j,))
                   for j in range(len(chaos_jobs))]
            for th in cts:
                th.start()
            deadline = time.monotonic() + 60
            while (not any(gws[n].engine.active_slots
                           or gws[n].engine.queue_depth
                           for n in names)
                   and time.monotonic() < deadline):
                time.sleep(0.001)
            fleet.kill(kill_first)
            fleet.kill(kill_second)
            for th in cts:
                th.join()
            failed = sum(1 for r in chaos_res
                         if r is None or r[0] is None)
            exact = sum(
                1 for j, r in enumerate(chaos_res)
                if r is not None
                and r[0] == want_for(chaos_jobs[j], 12))
            arm["chaos"] = {
                "killed": [kill_first, kill_second],
                "requests": len(chaos_jobs),
                "failed": failed,
                "exact": exact,
                "all_exact": exact == len(chaos_jobs),
                "migrations": fleet.migrations,
            }

            # --- post-kill probe: did the shared prefix survive? ----
            survivors = [n for n in names
                         if n not in (kill_first, kill_second)]

            def hit_tokens():
                return sum(gws[n].engine.stats()
                           .get("prefix_hit_tokens", 0) or 0
                           for n in survivors)

            before = hit_tokens()
            store_hits0 = (fleet.store.stats()["hits"]
                           if fleet.store else 0)
            t0 = time.perf_counter()
            ptoks, _ = fleet.submit_and_wait(
                "probe", list(probe), max_new_tokens=8,
                slo_class="interactive")
            probe_ms = (time.perf_counter() - t0) * 1e3
            arm["post_kill_probe"] = {
                "hit_ratio": round(max(0.0, min(1.0,
                    (hit_tokens() - before) / (len(probe) - 1))), 3),
                "exact": ptoks == want_for(probe, 8),
                "lat_ms": round(probe_ms, 1),
                "store_hits_delta": (
                    fleet.store.stats()["hits"] - store_hits0
                    if fleet.store else 0),
            }
            if disagg:
                snap = fleet.snapshot()
                arm["handoffs"] = snap["handoffs"]
                arm["store"] = snap["store"]
            return arm
        finally:
            fleet.close()

    symmetric = run_arm(False)
    disagg = run_arm(True)
    return {
        "metric": "serving_disagg_storm",
        "model": f"llama-{preset}" + (f" {quant}" if quant else " bf16")
                 + (f" {overrides}" if overrides else ""),
        "device": _device_tag(),
        "workload": {
            "victim_tenants": tenants,
            "reqs_per_tenant": reqs_per_tenant,
            "flood_threads": flood_threads,
            "flood_reqs": flood_reqs,
            "shared_prefix_len": shared_len,
            "long_prefill_len": long_len,
            "budgets": list(budgets),
            "slots": slots, "slot_len": slot_len,
            "block_size": block_size,
            "num_blocks": num_blocks,
            "replicas": replicas,
            "store_mb": store_mb,
        },
        "arms": {"symmetric": symmetric, "disagg": disagg},
        "disagg_wins_interactive_p95": bool(
            disagg["interactive_p95_ms"] is not None
            and symmetric["interactive_p95_ms"] is not None
            and disagg["interactive_p95_ms"]
            <= symmetric["interactive_p95_ms"]),
        "disagg_wins_useful_tok": bool(
            disagg["useful_tok_per_s"]
            >= symmetric["useful_tok_per_s"]),
        "prefix_survives_death": bool(
            disagg["post_kill_probe"]["hit_ratio"]
            > max(0.5, symmetric["post_kill_probe"]["hit_ratio"])),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("campaign", choices=["serve", "spec", "decode",
                                         "storm", "prefix_storm",
                                         "disagg_storm"])
    ap.add_argument("--preset", default="bench_1b")
    ap.add_argument("--quant", choices=["int8", "int4"], default=None)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--train-steps", type=int, default=60)
    # decode campaign: measurement shape + host-sized config overrides
    # (recorded in the output — a CPU host can't time 7B honestly)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--hidden", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    # storm campaign knobs
    ap.add_argument("--tenants", type=int, default=6)
    ap.add_argument("--reqs-per-tenant", type=int, default=8)
    ap.add_argument("--flood-threads", type=int, default=12)
    ap.add_argument("--flood-reqs", type=int, default=72)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--slot-len", type=int, default=128)
    ap.add_argument("--slo-ms", type=float, default=2000.0)
    ap.add_argument("--qps", type=float, default=25.0,
                    help="per-tenant admitted request rate (storm)")
    ap.add_argument("--burst", type=int, default=30)
    # prefix_storm campaign knobs
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-KV block size (prefix_storm)")
    ap.add_argument("--shared-len", type=int, default=88,
                    help="shared system-prompt length (prefix_storm)")
    ap.add_argument("--replicas", type=int, default=3,
                    help="fleet size for the chaos arm (prefix_storm) "
                         "/ total fleet size per arm (disagg_storm)")
    # disagg_storm campaign knobs
    ap.add_argument("--store-mb", type=int, default=64,
                    help="GlobalBlockStore byte budget in MiB "
                         "(disagg_storm)")
    ap.add_argument("--long-len", type=int, default=None,
                    help="long-prefill prompt length; default "
                         "min(2*shared_len, slot_len-24) (disagg_storm)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="per-engine KV pool size in blocks; small "
                         "pools force eviction + store promotion "
                         "(disagg_storm)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON to this path")
    ap.add_argument("--jaxcheck-out", default=None,
                    help="enable the jit-cache sentinel for the "
                         "campaign and write its report (signature "
                         "counts, limits, witnesses) here; exits "
                         "nonzero if any entry exceeded its bucket "
                         "bound")
    args = ap.parse_args()
    if args.jaxcheck_out:
        from kubeflow_rm_tpu.analysis.jaxcheck import recompile
        recompile.set_enabled(True)
        recompile.reset()
    if args.campaign == "serve":
        out = serve_campaign(args.preset, args.quant, args.requests,
                             args.concurrency, args.max_new)
    elif args.campaign == "spec":
        out = spec_campaign(args.preset, args.train_steps, args.max_new)
    elif args.campaign == "decode":
        overrides = {k: v for k, v in {
            "dim": args.dim, "n_layers": args.layers,
            "hidden_dim": args.hidden,
            "max_seq_len": args.seq_len}.items() if v is not None}
        out = decode_campaign(args.preset, args.batch, args.prompt_len,
                              args.max_new, overrides)
    elif args.campaign == "prefix_storm":
        overrides = {k: v for k, v in {
            "dim": args.dim, "n_layers": args.layers,
            "hidden_dim": args.hidden,
            "max_seq_len": args.seq_len}.items() if v is not None}
        out = prefix_storm_campaign(
            args.preset, args.quant, args.tenants,
            args.reqs_per_tenant, args.flood_threads, args.flood_reqs,
            args.slots, args.slot_len, args.block_size,
            args.shared_len, args.replicas, overrides)
    elif args.campaign == "disagg_storm":
        overrides = {k: v for k, v in {
            "dim": args.dim, "n_layers": args.layers,
            "hidden_dim": args.hidden,
            "max_seq_len": args.seq_len}.items() if v is not None}
        out = disagg_storm_campaign(
            args.preset, args.quant, args.tenants,
            args.reqs_per_tenant, args.flood_threads, args.flood_reqs,
            args.slots, args.slot_len, args.block_size,
            args.shared_len, args.replicas, args.store_mb,
            long_len=args.long_len, num_blocks=args.num_blocks,
            overrides=overrides)
    else:
        overrides = {k: v for k, v in {
            "dim": args.dim, "n_layers": args.layers,
            "hidden_dim": args.hidden,
            "max_seq_len": args.seq_len}.items() if v is not None}
        out = storm_campaign(args.preset, args.quant, args.tenants,
                             args.reqs_per_tenant, args.flood_threads,
                             args.flood_reqs, args.slots, args.slot_len,
                             args.slo_ms, args.qps, args.burst,
                             overrides)
    # shared artifact header: ratchet.py refuses to diff storms whose
    # arm flags (campaign/preset/load shape) disagree
    import os

    from kubeflow_rm_tpu.controlplane.obs.runmeta import build_run_meta
    interleave = os.environ.get("KFRM_RUN_INTERLEAVE")
    # every campaign's line names the device it ran on: a cpu row is a
    # rehearsal, never a chip result
    out.setdefault("device", _device_tag())
    out["run_meta"] = build_run_meta(
        "serve_bench",
        {
            "campaign": args.campaign, "preset": args.preset,
            "quant": args.quant, "tenants": args.tenants,
            "reqs_per_tenant": args.reqs_per_tenant,
            "flood_threads": args.flood_threads, "slots": args.slots,
            "slo_ms": args.slo_ms, "qps": args.qps,
            "slot_len": args.slot_len, "block_size": args.block_size,
            "shared_len": args.shared_len, "replicas": args.replicas,
            "store_mb": args.store_mb, "long_len": args.long_len,
            "num_blocks": args.num_blocks,
        },
        interleave_index=int(interleave) if interleave else None)
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    if args.jaxcheck_out:
        from kubeflow_rm_tpu.analysis.jaxcheck import recompile
        findings = recompile.over_limit()
        audit = {
            "run_meta": out.get("run_meta"),
            "report": recompile.report(),
            "over_limit": findings,
        }
        with open(args.jaxcheck_out, "w") as f:
            json.dump(audit, f, indent=1)
        if findings:
            print(f"jaxcheck: {len(findings)} jit entries over their "
                  f"recompile limit (see {args.jaxcheck_out})",
                  file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
