"""Kind ``serve_qwen3_next``: kind ``serve``'s open loop (``perf/kinds/
serve.py``: the same fleet, gateway and engine, the same offering of
requests, stamps and sweep, imported from there; the device counters
read at the window's two ends as ``serve_hybrid`` reads them) for a
configuration of the ``qwen3_next`` family, whose cache keeps a
delta-rule state a slot beside the paged keys and values of its full
attention layers and whose expert layers hold a share of the experts.

What differs from kind ``serve_hybrid``: the program's config is built
from this family's keys (``qwen3_next_config``; the family's module is
imported here and nowhere the other cells load), and the sample of
what was served is read by ``perf/reference_qwen3_next.py``, whose
head yields per column the gap of the served token and the argmax
rather than the (rows, vocabulary) logits.
"""

import gc
import sys
import threading
import time

import numpy as np

from perf import flops, harness, stamps, traffic_gen
from perf import reference_qwen3_next as reference
from perf.kinds import serve
from perf.kinds.serve_hybrid import _counters, _window


def qwen3_next_config(config: dict):
    """The program's ``Qwen3NextConfig`` for the configuration file.
    What the file lists as ``assumed`` is held to what the program
    builds: a corrected value there has to come with the code."""
    import jax.numpy as jnp

    from kubeflow_rm_tpu.models.qwen3_next import Qwen3NextConfig
    built = {"norm": "zero_centred", "shared_expert_gate": "sigmoid",
             "attention_output_gate": "sigmoid",
             "rotary": "split_halves_first_rotary_dims",
             "qk_l2norm_and_scale": "l2norm_q_scaled_by_key_dim_rsqrt",
             "conv_bias": False, "norm_topk_prob": True,
             "hidden_act": "silu", "decoder_sparse_step": 1,
             "mlp_only_layers": []}
    for key, value in built.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the program "
                             f"builds {value!r}")
    prec = config["precision"]
    if prec["state"] != "float32":
        raise ValueError("the delta-rule state is kept in float32")
    return Qwen3NextConfig(
        n_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        chunk_size=config["chunk_size"],
        n_routed_experts=config["router_width"],
        experts_held=(config["experts_held_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["shared_expert_intermediate_size"],
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(prec["compute"]),
        param_dtype=jnp.dtype(prec["params"]))


def run(*, cell, args, devices, clock, t_start, dry) -> dict:
    import jax

    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import (
        ServingGateway, TenantPolicy,
    )
    from kubeflow_rm_tpu.models.generate import (
        ContinuousBatchingEngine, _bucket_len,
    )
    from kubeflow_rm_tpu.models.qwen3_next import init_params

    config, mix = cell["config"], cell["traffic"]
    cfg = qwen3_next_config(config)
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
        "queue_ms": serve._felt(felt["queue_ms"], dry),
        "generator_late_ms_max": 1e3 * max(late) if late else None,
        "decode_steps": counters["decode_steps"],
        "prefills": counters["prefills"],
        "latency_p50_ms": traffic_gen.percentile(lat_ms, 0.5),
        "latency_tail_ms": stamps.latency_tail(records, t0),
        "compile_programs_total": clock.programs,
        "compile_s_total": clock.seconds,
        # the expert layers' own counts over the window
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
    """``serve_hybrid._check``'s two readings against this family's
    reference: over a sample drawn from the seed of the requests the
    window finished, the longest among them, each served token's logit
    lies some gap below the reference's best; the **mean** gap over
    the tokens compared and the **number of tokens whose gap is over**
    ``check.far_gap`` are compared, the widest goes to ``info`` (the
    mix's ``check.why`` says why).

    ``--control`` takes one name or several, comma-separated: the
    reference at eight bits (``int8``) or with one of its planted
    faults (``reference.FAULTS``) picks in the program's place; the
    first goes through the verdict, every one's readings and the
    limits it fell by go to ``info["controls"]``."""
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
    lengths = np.zeros((len(picks),), np.int32)
    for b, r in enumerate(picks):
        prompt = requests[index[id(r)]]["prompt"]
        n, new = len(prompt), len(r["tokens"])
        rows[b, :n] = prompt
        rows[b, n:n + new - 1] = r["tokens"][:-1]
        chosen[b, n - 1:n - 1 + new] = r["tokens"]
        lengths[b] = n + new - 1
    t_ref = time.perf_counter()
    weights = reference.init_weights(
        d, args.seed, jnp.dtype(config["precision"]["params"]))
    x = reference.final_hidden(weights, rows, d, lengths=lengths)
    n_compared = int((chosen >= 0).sum())
    far = mix["check"]["far_gap"]

    def readings(picked):
        gaps, _, big = reference.head_readings(weights, x, d, picked)
        return {"served_token_gap_mean": float(gaps.sum()) / n_compared,
                "served_tokens_far": int((gaps > far).sum()),
                "served_token_gap": float(gaps.max())}, big

    own, big = readings(chosen)
    compared = {k: {"value": own[k], "limit": limits[k]} for k in limits}
    info = {"tokens_compared": n_compared,
            "served_token_gap": own["served_token_gap"],
            "requests_compared": len(picks), "logit_abs_max": big}
    if args.control:
        names = args.control.split(",")
        info["controls"] = {}
        for name in names:
            low = reference.final_hidden(weights, rows, d, quant=name,
                                         lengths=lengths)
            _, top, _ = reference.head_readings(weights, low, d, chosen,
                                                quant=name)
            del low
            read, _ = readings(np.where(chosen >= 0, np.asarray(top), -1))
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
