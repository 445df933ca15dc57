"""Kind ``serve_hybrid``: kind ``serve``'s open loop (``perf/kinds/
serve.py``: the same fleet, gateway and engine, the same offering of
requests, stamps and sweep, imported from there) for a configuration
of the ``nemotron_h`` family, whose cache keeps a recurrent state a
slot beside the paged keys and values and whose expert layers hold a
share of the experts.

What differs from kind ``serve``: the program's config is built from
this family's keys (``nemotron_config``); the engine's device counters
(``engine.device_counters()``, one transfer) are read at the window's
two ends beside the host's; and the sample of what was served is read
by ``perf/reference_nemotron_h.py``.
"""

import gc
import sys
import threading
import time

import numpy as np

from perf import flops, harness, stamps, traffic_gen
from perf import reference_nemotron_h as reference
from perf.kinds import serve
from perf.reference import served_gaps


def nemotron_config(config: dict):
    """The program's ``NemotronHConfig`` for the configuration file.
    What the file lists as ``assumed`` is held to what the program
    builds: a corrected value there has to come with the code."""
    import jax.numpy as jnp

    from kubeflow_rm_tpu.models.nemotron_h import NemotronHConfig
    built = {"attention_rotary": False, "router_reads": "hidden",
             "shared_expert_reads": "hidden",
             "between_latent_projection_and_experts": "nothing"}
    for key, value in built.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the program "
                             f"builds {value!r}")
    prec = config["precision"]
    if prec["state"] != "float32":
        raise ValueError("the recurrent state is kept in float32")
    return NemotronHConfig(
        pattern=config["hybrid_override_pattern"],
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"], state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        n_routed_experts=config["router_width"],
        experts_held=(config["experts_held_first"],
                      config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        routed_scaling=float(config["routed_scaling_factor"]),
        latent_dim=config["moe_latent_size"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["moe_shared_expert_intermediate_size"],
        norm_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(prec["compute"]),
        param_dtype=jnp.dtype(prec["params"]))


def _counters(gateway) -> dict:
    """The host's counters and the device's, the latter by the one
    transfer a window's end may cost (through the gateway: under the
    lock its drain thread steps the engine with)."""
    engine = gateway.engine
    s = engine.stats()
    return {**serve._counters(engine), **gateway.device_counters(),
            "host_syncs_total": s["host_syncs_total"],
            "prefix_hits_refused_total": s["prefix_hits_refused_total"]}


def _window(fleet, gateway, tenant, requests, t0, seconds, timeout_s,
            annotate):
    """``serve._window`` with the device counters read at both ends."""
    before = _counters(gateway)
    records, threads = serve._offer(fleet, tenant, requests, t0, timeout_s,
                                    annotate)
    harness.sleep_until(t0 + seconds)
    window_s = time.perf_counter() - t0
    after = _counters(gateway)
    for t in threads:
        t.join(timeout_s + 60.0)
    return records, before, after, window_s


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
    cfg = nemotron_config(config)
    sv = config["serving"]
    d = reference.dims_of(config)
    annotate = jax.profiler.TraceAnnotation

    # ---- set-up ------------------------------------------------------
    make = jax.jit(lambda key: init_params(cfg, key))
    engine = ContinuousBatchingEngine(
        make(jax.random.key(args.seed)), cfg, slots=sv["slots"],
        slot_len=sv["slot_len"], block_size=sv["block_size"])
    tenant = mix["tenant"]["name"]
    policy = TenantPolicy(**mix["tenant"]["policy"])
    gateway = ServingGateway(engine, policies={tenant: policy},
                             max_queue=mix["gateway"]["max_queue"])
    fleet = ServingFleet({"r0": gateway})
    timeout_s = float(mix["request_timeout_s"])
    try:
        requests = traffic_gen.serve_requests(mix, args.seed, args.seconds,
                                              cfg.vocab_size)
        sweep = (serve._sweep_requests(mix, args, cfg) if args.sweep
                 else [])
        warm = traffic_gen.warmup_requests(
            mix, [len(r["prompt"]) for rs in [requests] + [w for _, w in sweep]
                  for r in rs],
            sv["slots"], cfg.vocab_size, _bucket_len)
        records, threads = serve._offer(fleet, tenant, warm,
                                        time.perf_counter(), 1100.0,
                                        annotate)
        for t in threads:
            t.join()
        bad = [r["error"] for r in records if r["done_s"] is None]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        gateway.device_counters()       # the fetch itself, once, warm
        tracer = harness.TraceSlice() if args.trace else None
        gc.collect()

        if args.sweep:
            return serve._sweep(fleet, engine, tenant, sweep, args,
                                timeout_s, annotate, clock)

        # ---- the window ----------------------------------------------
        compiled_before = clock.programs
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        timer = None
        if tracer:
            at = mix["trace"]["start_frac"] * args.seconds
            timer = threading.Thread(
                target=serve._trace_slice,
                args=(tracer, t0 + at, mix["trace"]["seconds"]),
                daemon=True)
            timer.start()
        records, before, after, window_s = _window(
            fleet, gateway, tenant, requests, t0, args.seconds, timeout_s,
            annotate)
        compiles = clock.programs - compiled_before
        if timer:
            timer.join()
        memory_peak = harness.memory_peak(devices)
        engine_stats = engine.stats()
    finally:
        fleet.close()

    # ---- free the program, then the reference reads a sample ---------
    del fleet, gateway, engine, make
    gc.collect()
    done = [r for r in records if r["done_s"] is not None]
    failed = len(records) - len(done)
    for r in records:
        if r["error"]:
            print(f"request failed: {r['error']}", file=sys.stderr)
            break
    compared, info = _check(done, requests, records, config, mix, d,
                            args)

    # ---- metrics -----------------------------------------------------
    lat_ms = [1e3 * (r["done_s"] - r["due_s"]) for r in done]
    stamped = stamps.in_window(records, t0, window_s)
    for why in stamped["malformed"]:
        print(f"malformed timeline: {why}", file=sys.stderr)
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
        "completed_in_window": len(in_window),
        "ttft_ms": serve._felt(felt["ttft_ms"], dry),
        "itl_ms": serve._felt(felt["itl_ms"], dry),
        "generator_late_ms_max": 1e3 * max(late) if late else None,
        "decode_steps": counters["decode_steps"],
        "prefills": counters["prefills"],
        "latency_p50_ms": traffic_gen.percentile(lat_ms, 0.5),
        "latency_tail_ms": stamps.latency_tail(records, t0),
        "compile_programs_total": clock.programs,
        "compile_s_total": clock.seconds,
        # the expert layers' and the state's own counts over the window
        "device_counters": {k: counters[k] for k in counters
                            if k.endswith("_total") and "expert" in k
                            or "moe_steps" in k},
        "recurrent_state_bytes": engine_stats["recurrent_state_bytes"],
        "prefix_hits_refused": counters["prefix_hits_refused_total"],
        # one blocking transfer a step: no more than the steps taken
        # and the requests that left at a boundary without one
        "host_syncs": counters["host_syncs_total"],
    })
    return {"compared": compared, "compiles_in_window": compiles,
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "memory_peak_bytes": memory_peak,
            "trace": trace, "info": info}


def _check(done, requests, records, config, mix, d, args):
    """``serve._check`` against this family's reference, read by two
    other statistics. Over a sample drawn from the seed of the requests
    the window finished, the longest among them, each served token's
    logit lies some gap below the reference's best. Kind ``serve``
    compares the widest gap; here the **mean** gap over the tokens
    compared is compared, beside the **number of tokens whose gap is
    over** ``check.far_gap``, and the widest goes to ``info``. The
    routing of a sparse-expert layer is a discrete choice among close
    scores, so a rounding anywhere upstream can swap an expert, and the
    widest gap of some thousand tokens rides on single swaps: on the
    chip the program's widest readings reach the int8 control's lowest,
    while the means lie tenfold apart. The mean alone lets a rare fault
    by (one wrong token among 5,000 moves it by a thousandth of that
    token's gap); the count does not: a token picked from a wrong state
    lies units below the best, a swapped expert tenths (the mix's
    ``check.why``).

    ``--control`` takes one name or several, comma-separated: the
    first goes through the verdict, every one's readings and the limits
    it fell by go to ``info["controls"]``."""
    import jax.numpy as jnp

    limits = mix["check"]["limits"]
    if not done:
        return {k: {"value": None, "limit": v}
                for k, v in limits.items()}, {}
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
    n_compared = int((chosen >= 0).sum())

    far = mix["check"]["far_gap"]

    def readings(picked):
        gaps = served_gaps(logits, picked)
        return {"served_token_gap_mean": float(gaps.sum()) / n_compared,
                "served_tokens_far": int((gaps > far).sum()),
                "served_token_gap": float(gaps.max())}

    own = readings(jnp.asarray(chosen))
    compared = {k: {"value": own[k], "limit": limits[k]} for k in limits}
    info = {"tokens_compared": n_compared,
            "served_token_gap": own["served_token_gap"],
            "requests_compared": len(picks),
            "logit_abs_max": float(jnp.abs(logits).max())}
    if args.control:
        # a control: the reference at the lower precision, or with a
        # fault planted in it, put in the program's place; the gap of
        # the token it puts first goes through the same readings, and
        # the program's own go to info
        names = args.control.split(",")
        info["controls"] = {}
        for name in names:
            low = reference.forward_logits(weights, rows, d, quant=name)
            first = jnp.where(jnp.asarray(chosen) >= 0,
                              jnp.argmax(low, -1).astype(jnp.int32), -1)
            del low
            read = readings(first)
            info["controls"][name] = {
                **read, "not_correct_by": [k for k in limits
                                           if not read[k] <= limits[k]]}
        control = info["controls"][names[0]]
        info.update({"control": args.control,
                     "served_token_gap": control["served_token_gap"],
                     **{"program_" + k: v for k, v in own.items()}})
        for k in compared:
            compared[k]["value"] = control[k]
    info["reference_s"] = time.perf_counter() - t_ref
    return compared, info
