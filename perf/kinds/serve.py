"""Kind ``serve``: an open loop of requests through
``ServingFleet.submit_and_wait`` on a one-replica fleet ->
``ServingGateway`` -> ``ContinuousBatchingEngine(paged=True)``.

Grown from ``chip_smoke.py``'s ``serve_phase`` (copied, not imported).
Set-up makes the weights on the device in one jitted call, builds the
engine, gateway and fleet, and sends one round of warm-up requests
that reach every prefill bucket of the mix and every decode slot. The
window then offers the mix's requests at their due times, one thread
each (the API blocks until the whole answer is there), and the run
waits for every request that was due. Once the program's state is
freed, the plain reference reads a sample of what was served.
"""

import gc
import sys
import threading
import time

import numpy as np

from perf import flops, harness, reference, stamps, traffic_gen


def llama_config(config: dict):
    """The program's ``LlamaConfig`` for a ``config.json``."""
    import jax.numpy as jnp

    from kubeflow_rm_tpu.models import LlamaConfig
    prec = config["precision"]
    return LlamaConfig(
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        hidden_dim=config["intermediate_size"],
        max_seq_len=config["max_position_embeddings"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(prec["compute"]),
        param_dtype=jnp.dtype(prec["params"]))


def _counters(engine) -> dict:
    s = engine.stats()
    return {"decode_steps": s["decode_steps"], "prefills": s["prefills"],
            "occupancy_sum": engine.occupancy_sum,
            "admitted_total": s["admitted_total"],
            "finished_total": s["finished_total"],
            "prompt_tokens": s.get("prompt_tokens", 0),
            "prefix_hit_tokens": s.get("prefix_hit_tokens", 0)}


def _offer(fleet, tenant, requests, t0, timeout_s, annotate):
    """Send each request at ``t0 + due_s`` on a thread of its own;
    return the records and the threads. A record's ``done_s`` is set
    only where the whole answer came back, and its ``timeline`` is
    then the engine's stamps of that answer (``perf/stamps.py``)."""
    records = [{"due_s": r["due_s"], "prompt_len": len(r["prompt"]),
                "max_new_tokens": r["max_new_tokens"], "tokens": None,
                "error": None, "done_s": None, "sent_s": None,
                "timeline": None}
               for r in requests]

    def client(i):
        rec, req = records[i], requests[i]
        rec["sent_s"] = time.perf_counter() - t0
        try:
            with annotate("perf.submit_and_wait"):
                tokens, info = fleet.submit_and_wait(
                    tenant, req["prompt"],
                    max_new_tokens=req["max_new_tokens"],
                    timeout_s=timeout_s)
            if tokens is None:
                rec["error"] = f"shed: {info.get('reason')}"
            elif len(tokens) != req["max_new_tokens"]:
                rec["error"] = (f"{len(tokens)} of "
                                f"{req['max_new_tokens']} tokens")
            else:
                rec["tokens"] = [int(t) for t in tokens]
                rec["timeline"] = info.get("timeline")
                rec["done_s"] = time.perf_counter() - t0
        except Exception as e:          # counted as failed, and shown
            rec["error"] = repr(e)

    threads = []
    for i, req in enumerate(requests):
        harness.sleep_until(t0 + req["due_s"])
        t = threading.Thread(target=client, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    return records, threads


def run(*, cell, args, devices, clock, t_start, dry) -> dict:
    import jax

    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import (
        ServingGateway, TenantPolicy,
    )
    from kubeflow_rm_tpu.models import init_params
    from kubeflow_rm_tpu.models.generate import (
        ContinuousBatchingEngine, _bucket_len,
    )

    config, mix = cell["config"], cell["traffic"]
    cfg = llama_config(config)
    sv = config["serving"]
    d = reference.dims_of(config)
    annotate = jax.profiler.TraceAnnotation

    # ---- set-up ------------------------------------------------------
    make = jax.jit(lambda key: init_params(cfg, key))
    engine = ContinuousBatchingEngine(
        make(jax.random.key(args.seed)), cfg, paged=sv["paged"],
        slots=sv["slots"], slot_len=sv["slot_len"],
        block_size=sv["block_size"])
    tenant = mix["tenant"]["name"]
    policy = TenantPolicy(**mix["tenant"]["policy"])
    fleet = ServingFleet({"r0": ServingGateway(
        engine, policies={tenant: policy},
        max_queue=mix["gateway"]["max_queue"])})
    timeout_s = float(mix["request_timeout_s"])
    try:
        requests = traffic_gen.serve_requests(mix, args.seed, args.seconds,
                                              cfg.vocab_size)
        sweep = _sweep_requests(mix, args, cfg) if args.sweep else []
        warm = traffic_gen.warmup_requests(
            mix, [len(r["prompt"]) for rs in [requests] + [w for _, w in sweep]
                  for r in rs],
            sv["slots"], cfg.vocab_size, _bucket_len)
        records, threads = _offer(fleet, tenant, warm, time.perf_counter(),
                                  1100.0, annotate)
        for t in threads:
            t.join()
        bad = [r["error"] for r in records if r["done_s"] is None]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        tracer = harness.TraceSlice() if args.trace else None
        gc.collect()

        if args.sweep:
            return _sweep(fleet, engine, tenant, sweep, args, timeout_s,
                          annotate, clock)

        # ---- the window ----------------------------------------------
        compiled_before = clock.programs
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        timer = None
        if tracer:
            at = mix["trace"]["start_frac"] * args.seconds
            timer = threading.Thread(
                target=_trace_slice,
                args=(tracer, t0 + at, mix["trace"]["seconds"]),
                daemon=True)
            timer.start()
        records, before, after, window_s = _window(
            fleet, engine, tenant, requests, t0, args.seconds, timeout_s,
            annotate)
        compiles = clock.programs - compiled_before
        if timer:
            timer.join()
        memory_peak = harness.memory_peak(devices)
    finally:
        fleet.close()

    # ---- free the program, then the reference reads a sample ---------
    del fleet, engine, make
    gc.collect()
    done = [r for r in records if r["done_s"] is not None]
    failed = len(records) - len(done)
    for r in records:
        if r["error"]:
            print(f"request failed: {r['error']}", file=sys.stderr)
            break
    compared, info = _check(done, requests, records, config, mix, d,
                            args, dry)

    # ---- metrics -----------------------------------------------------
    lat_ms = [1e3 * (r["done_s"] - r["due_s"]) for r in done]
    stamped = stamps.in_window(records, t0, window_s)
    for why in stamped["malformed"]:
        print(f"malformed timeline: {why}", file=sys.stderr)
    # a whole answer whose stamps cannot place its tokens fails the run
    compared["malformed_timelines"] = {
        "value": len(stamped["malformed"]), "limit": 0}
    counters = {k: after[k] - before[k] for k in after}
    counters["slots"] = sv["slots"]
    metrics = {
        "req_latency_p95_ms": traffic_gen.percentile(lat_ms, 0.95),
        "serve_tok_s": stamped["tokens"] / window_s,
        "setup_s": setup_s,
    }
    t_reduce = time.perf_counter()
    trace = tracer.reduce(args.dump_trace) if tracer else None
    if args.trace:
        run_ctx = {
            "cell": cell, "dims": d, "window_s": window_s,
            "counters": counters, "requests": records,
            "stamped": stamped, "trace": trace,
            "peaks": None if dry else flops.peaks(devices[0].device_kind),
            "t0": t0,
        }
        metrics.update(harness.read_per_layer(cell, run_ctx))
        info["notes"] = run_ctx["notes"]
    late = [r["sent_s"] - r["due_s"] for r in records
            if r["sent_s"] is not None]
    felt = stamps.waits(records, t0)
    in_window = [r for r in done if r["done_s"] <= window_s]
    info.update({
        "trace_reduce_s": time.perf_counter() - t_reduce,
        "requests_due": len(records),
        "tokens_stamped_in_window": stamped["tokens"],
        "answers_with_tokens_in_window": len(stamped["spans"]),
        # the reading before PR 34, whole answers that ended before the
        # close, to be followed beside the stamped count
        "completed_in_window": len(in_window),
        "tokens_completed_in_window": sum(len(r["tokens"])
                                          for r in in_window),
        "done_near_close_s": sorted(round(r["done_s"] - window_s, 2)
                                    for r in done
                                    if abs(r["done_s"] - window_s) < 2.0),
        # what a streaming tenant would feel (p50, p95, samples): in
        # info, not metrics, until a benchmark PR has seen their spread
        "ttft_ms": _felt(felt["ttft_ms"], dry),
        "itl_ms": _felt(felt["itl_ms"], dry),
        "generator_late_ms_max": 1e3 * max(late) if late else None,
        "decode_steps": counters["decode_steps"],
        "prefills": counters["prefills"],
        "latency_p50_ms": traffic_gen.percentile(lat_ms, 0.5),
        "latency_tail_ms": stamps.latency_tail(records, t0),
        "compile_programs_total": clock.programs,
        "compile_s_total": clock.seconds,
    })
    return {"compared": compared, "compiles_in_window": compiles,
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "memory_peak_bytes": memory_peak,
            "trace": trace, "info": info}


def _felt(values_ms, dry):
    """p50, p95 and the sample count; the rehearsal keeps the count."""
    s = stamps.summary(values_ms)
    return {"p50": None, "p95": None, "n": s["n"]} if dry else s


def _window(fleet, engine, tenant, requests, t0, seconds, timeout_s,
            annotate):
    """Offer ``requests`` from ``t0``; read the engine's counters at
    the window's two ends; then wait for every request that was due:
    one that comes late is late, not wrong."""
    before = _counters(engine)
    records, threads = _offer(fleet, tenant, requests, t0, timeout_s,
                              annotate)
    harness.sleep_until(t0 + seconds)
    after = _counters(engine)
    window_s = time.perf_counter() - t0
    for t in threads:
        t.join(timeout_s + 60.0)
    return records, before, after, window_s


def _sweep_requests(mix, args, cfg):
    """[(rate, requests)] for ``--sweep``: the mix at each rate;
    ``rate@n`` puts the sizes in the order of ``schedule_seed`` n."""
    import copy
    out = []
    for i, item in enumerate(args.sweep.split(",")):
        rate, _, order = item.partition("@")
        rate = float(rate)
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_per_s"] = rate
        if order:
            m["schedule_seed"] = int(order)
        # another seed a rate: a prompt sent twice would hit the cache
        out.append((item, traffic_gen.serve_requests(
            m, args.seed + i, args.seconds, cfg.vocab_size)))
    return out


def _sweep(fleet, engine, tenant, sweep, args, timeout_s, annotate, clock):
    """Bring-up only: one set-up, then a window at each of the rates
    given, to find the knee. No comparison with the reference."""
    rows = []
    for rate, requests in sweep:
        programs = clock.programs
        t0 = time.perf_counter()
        records, before, after, window_s = _window(
            fleet, engine, tenant, requests, t0, args.seconds, timeout_s,
            annotate)
        done = [r for r in records if r["done_s"] is not None]
        lat = [1e3 * (r["done_s"] - r["due_s"]) for r in done]
        inside = [r for r in done if r["done_s"] <= window_s]
        steps = after["decode_steps"] - before["decode_steps"]
        stamped = stamps.in_window(records, t0, window_s)
        felt = stamps.waits(records, t0)
        rows.append({
            "rate_per_s": rate, "due": len(records),
            "failed": len(records) - len(done),
            "malformed": len(stamped["malformed"]),
            "serve_tok_s": stamped["tokens"] / window_s,
            "done_in_window": len(inside),
            "whole_answers_tok_s": (sum(len(r["tokens"]) for r in inside)
                                    / window_s),
            "ttft_ms": stamps.summary(felt["ttft_ms"]),
            "itl_ms": stamps.summary(felt["itl_ms"]),
            "queue_ms": stamps.summary(felt["queue_ms"]),
            "latency_p50_ms": traffic_gen.percentile(lat, 0.5),
            "latency_p95_ms": traffic_gen.percentile(lat, 0.95),
            "last_done_s": max((r["done_s"] for r in done), default=None),
            # answers that came within 4 s of the close, before (-) or
            # after it: one that comes at the close makes serve_tok_s
            # step from run to run
            "done_near_close_s": sorted(
                round(r["done_s"] - window_s, 2) for r in done
                if abs(r["done_s"] - window_s) < 4.0),
            "decode_step_ms": 1e3 * window_s / steps if steps else None,
            "occupancy": ((after["occupancy_sum"] - before["occupancy_sum"])
                          / (steps * engine.slots) if steps else None),
            "compiles": clock.programs - programs})
        print(f"sweep {rows[-1]}", file=sys.stderr, flush=True)
    return {"sweep": rows}


def _trace_slice(tracer, at, seconds):
    harness.sleep_until(at)
    tracer.start()
    harness.sleep_until(at + seconds)
    tracer.stop()


def _check(done, requests, records, config, mix, d, args, dry):
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample drawn from the seed of the
    requests the window finished, the longest among them. Greedy
    tokens only, which is all this kind sends."""
    import jax.numpy as jnp

    limit = mix["check"]["limits"]["served_token_gap"]
    if not done:
        return {"served_token_gap": {"value": None, "limit": limit}}, {}
    index = {id(r): i for i, r in enumerate(records)}
    rng = np.random.default_rng(args.seed + 1)
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    others = [r for r in done if r is not longest]
    take = min(len(others), mix["check"]["sample"] - 1)
    picks = [longest] + [others[i] for i in
                         rng.choice(len(others), take, replace=False)]
    # one shape whatever the seed drew, so that the reference's own
    # programs are found in the compile cache
    T = mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
    rows = np.zeros((len(picks), T), np.int32)
    chosen = np.full((len(picks), T), -1, np.int32)
    for b, r in enumerate(picks):
        prompt = requests[index[id(r)]]["prompt"]
        n, new = len(prompt), len(r["tokens"])
        rows[b, :n] = prompt
        rows[b, n:n + new - 1] = r["tokens"][:-1]
        chosen[b, n - 1:n - 1 + new] = r["tokens"]
    t_ref = time.perf_counter()
    weights = reference.init_weights(
        d, args.seed, jnp.dtype(config["precision"]["params"]))
    logits = reference.forward_logits(weights, rows, d)
    gaps = reference.served_gaps(logits, jnp.asarray(chosen))
    gap = float(gaps.max())
    compared = {"served_token_gap": {"value": gap, "limit": limit}}
    info = {"tokens_compared": int((chosen >= 0).sum()),
            "requests_compared": len(picks),
            "logit_abs_max": float(jnp.abs(logits).max())}
    if args.control:
        # the control: the reference at the lower precision, put in
        # the program's place; the gap of the token it puts first goes
        # through the same verdict, and the program's own reading to info
        low = reference.forward_logits(weights, rows, d, quant=args.control)
        first = jnp.where(jnp.asarray(chosen) >= 0,
                          jnp.argmax(low, -1).astype(jnp.int32), -1)
        info.update({"control": args.control, "program_served_token_gap": gap})
        compared["served_token_gap"]["value"] = float(
            reference.served_gaps(logits, first).max())
        del low
    info["reference_s"] = time.perf_counter() - t_ref
    return compared, info
