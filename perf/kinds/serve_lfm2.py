"""Kind ``serve_lfm2``: kind ``serve``'s open loop (``perf/kinds/
serve.py``: the same fleet, gateway and engine, the same offering of
requests, stamps and sweep, imported from there; the device counters
read at the window's two ends as ``serve_hybrid`` reads them) for a
configuration of the ``lfm2_moe`` family, whose cache keeps a
convolution tail a slot and conv layer beside the paged keys and
values of its attention layers, and whose expert layers hold every
expert.

The comparison is ``serve_afmoe``'s, of logits: once the window's
requests are all back and the gateway is closed, a sample of them is
fed again through the timed path's own programs at the timed sizes
(``serve_afmoe.replay``: ``paged_prefill``, ``paged_install``,
``paged_decode_step`` on the engine's weights and cache, the served
tokens teacher-forced, other requests of the window keeping
``check.live`` slots busy), and the logits the prefill gives and
those of every ``check.every``-th decode step are kept on the host;
the program is freed and ``perf/reference_lfm2.py``'s full forward
gives the same rows, running at every column the experts the program
chose there (the cache's log of them, ``HybridPagedCache.routes``) and
saying where its own float32 choice would have been another.
"""

import gc
import sys
import threading
import time

import numpy as np

from kubeflow_rm_tpu.models import lfm2     # the parent has none: fail fast
from perf import flops, harness, stamps, traffic_gen
from perf import reference_lfm2 as reference
from perf.kinds import serve
from perf.kinds.serve_afmoe import _sample, readings, replay
from perf.kinds.serve_hybrid import _window


def lfm2_config(config: dict):
    """The program's ``Lfm2MoeConfig`` for the configuration file. What
    the file lists as ``assumed`` is held to what the program builds: a
    corrected value there has to come with the code."""
    import jax.numpy as jnp

    built = {"model_type": "lfm2_moe", "conv_bias": False,
             "conv_chunk_order": ["B", "C", "x"], "conv_activation": "none",
             "norm_gain": "w", "qk_norm": "rms_a_head",
             "rope": "rotate_half_whole_head", "route_norm_eps": 1e-6,
             "norm_topk_prob": True, "use_expert_bias": True,
             "num_shared_experts": 0, "tie_word_embeddings": True}
    for key, value in built.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the program "
                             f"builds {value!r}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types names a type a layer")
    prec = config["precision"]
    if prec["router"] != "float32":
        raise ValueError("the router is kept in float32")
    return lfm2.Lfm2MoeConfig(
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        conv_kernel=config["conv_L_cache"],
        rope_theta=float(config["rope_theta"]),
        dense_dim=config["intermediate_size"],
        n_routed_experts=config["num_experts"],
        experts_held=(0, config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        route_scale=float(config["routed_scaling_factor"]),
        route_eps=float(config["route_norm_eps"]),
        norm_eps=float(config["norm_eps"]),
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

    config, mix = cell["config"], cell["traffic"]
    cfg = lfm2_config(config)
    sv = config["serving"]
    d = reference.dims_of(config)
    annotate = jax.profiler.TraceAnnotation

    # ---- set-up ------------------------------------------------------
    make = jax.jit(lambda key: lfm2.init_params(cfg, key))
    engine = ContinuousBatchingEngine(
        make(jax.random.key(args.seed)), cfg, slots=sv["slots"],
        slot_len=sv["slot_len"], block_size=sv["block_size"],
        num_blocks=sv["num_blocks"])
    tenant = mix["tenant"]["name"]
    policy = TenantPolicy(**mix["tenant"]["policy"])
    gateway = ServingGateway(engine, policies={tenant: policy},
                             max_queue=mix["gateway"]["max_queue"])
    fleet = ServingFleet({"r0": gateway})
    timeout_s = float(mix["request_timeout_s"])
    closed = False
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
        fleet.close()
        closed = True
        done = [r for r in records if r["done_s"] is not None]
        picks, fillers = _sample(done, requests, records, mix, args)
        t_replay = time.perf_counter()
        program_rows, at, busy = replay(engine, cfg, picks,
                                        mix["check"]["every"], fillers,
                                        mix["check"]["live"])
        routes = (program_routes(engine, busy["seats"][:len(picks)])
                  if picks else None)
        replay_s = time.perf_counter() - t_replay
    finally:
        if not closed:
            fleet.close()

    # ---- free the program, then the reference reads the sample -------
    del fleet, gateway, engine, make
    gc.collect()
    failed = len(records) - len(done)
    for r in records:
        if r["error"]:
            print(f"request failed: {r['error']}", file=sys.stderr)
            break
    compared, info = _check(picks, program_rows, at, routes, config, mix, d,
                            args)
    info["replay_s"] = replay_s
    info["replay_slots"] = busy

    # ---- metrics -----------------------------------------------------
    lat_ms = [1e3 * (r["done_s"] - r["due_s"]) for r in done]
    stamped = stamps.in_window(records, t0, window_s)
    for why in stamped["malformed"]:
        print(f"malformed timeline: {why}", file=sys.stderr)
    compared["malformed_timelines"] = {
        "value": len(stamped["malformed"]), "limit": 0}
    # the pairs' counters wrap at 2^32: their differences modulo it
    counters = {k: (after[k] - before[k]) % (1 << 32) if "positions" in k
                else after[k] - before[k] for k in after}
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
        "occupancy_mean": (counters["occupancy_sum"]
                           / counters["decode_steps"]
                           if counters["decode_steps"] else None),
        "latency_p50_ms": traffic_gen.percentile(lat_ms, 0.5),
        "latency_tail_ms": stamps.latency_tail(records, t0),
        "compile_programs_total": clock.programs,
        "compile_s_total": clock.seconds,
        # the programs' own counts over the window: the expert layers'
        # and the keys an attention layer attended
        "device_counters": {k: counters[k] for k in counters
                            if k.endswith("_total") and (
                                "expert" in k or "moe_steps" in k
                                or "positions" in k)},
        "recurrent_state_bytes": engine_stats["recurrent_state_bytes"],
        "kv_pool_bytes": engine_stats["kv_pool_bytes"],
        "prefix_hits_refused": counters["prefix_hits_refused_total"],
        # one blocking transfer a step: no more than the steps taken
        # and the requests that left at a boundary without one
        "host_syncs": counters["host_syncs_total"],
    })
    return {"compared": compared, "compiles_in_window": compiles,
            "attempted": len(records), "failed": failed,
            "metrics": metrics, "memory_peak_bytes": memory_peak,
            "trace": trace, "info": info}


def program_routes(engine, seats) -> np.ndarray:
    """The experts the program chose at every column of the slots
    ``seats``, as its cache logged them: (len(seats), strip, expert
    layers, top_k) int32 on the host."""
    import jax

    log = jax.device_get(engine.cache.routes[:, np.asarray(seats)])
    return np.moveaxis(np.asarray(log, np.int32), 0, 2)


def route_readings(chosen: dict, lengths: np.ndarray) -> dict:
    """What a pass that ran another's choice of experts says of it,
    over every real column and expert layer (``reference_lfm2
    .routed_hidden``): the share of choices that were not its own
    (``route_flip_share``), and by how much its own choice's ``s +
    bias`` sum led the one run, on average and at the widest
    (``route_gap_mean``, ``route_gap_max``)."""
    real = np.arange(chosen["flip"].shape[1])[None, :] < lengths[:, None]
    flip, gap = chosen["flip"][real], chosen["gap"][real]
    return {"route_flip_share": float(flip.mean()),
            "route_gap_mean": float(gap.mean()),
            "route_gap_max": float(gap.max())}


def _check(picks, program_rows, at, routes, config, mix, d, args):
    """The program's logits rows against the reference's full forward
    over the same teacher-forced columns, the reference running the
    experts the program chose (``routes``, the cache's log) and saying
    where its own choice was another (the mix's ``check.why`` says why
    each limit is what it is); the prefill's rows alone, the reading
    with the reference choosing for itself (``free_choice``, where the
    routing's flips are read too) and the second witness (the reference
    at bfloat16, read along its own choice) go to ``info``.

    ``--control`` takes one name or several, comma-separated: the
    reference at eight bits (``int8``) or with one of its planted faults
    (``reference.FAULTS``) in the program's place, read as the program
    is, along its own choice; the first goes through the verdict, every
    one's readings and the limits it fell by go to
    ``info["controls"]``."""
    import jax.numpy as jnp

    limits = mix["check"]["limits"]
    if not picks:
        return {k: {"value": None, "limit": v}
                for k, v in limits.items()}, {}
    # one shape whatever the seed drew, so that the reference's own
    # programs are found in the compile cache
    T = mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
    tokens = np.zeros((mix["check"]["sample"], T), np.int32)
    lengths = np.zeros((mix["check"]["sample"],), np.int32)
    forced = np.zeros(tokens.shape + routes.shape[2:], np.int32)
    for b, (prompt, served) in enumerate(picks):
        seq = prompt + served[:-1]
        tokens[b, :len(seq)] = seq
        lengths[b] = len(seq)
        forced[b, :len(seq)] = routes[b, :len(seq)]
    t_ref = time.perf_counter()
    weights = reference.init_weights(
        d, args.seed, jnp.dtype(config["precision"]["params"]))

    def read(quant=None, choice=None):
        x, chosen = reference.routed_hidden(weights, tokens, d, quant=quant,
                                            lengths=lengths, routes=choice)
        return reference.head_logits(weights, x, d, at, quant=quant), chosen

    def against(rows, choice):
        """Rows of a side that chose ``choice`` against the float32
        reference running the same experts."""
        want, chosen = read(choice=choice)
        return {**readings(rows, want), **route_readings(chosen, lengths)}, want

    own, want = against(program_rows, forced)
    first = np.asarray([t == len(picks[b][0]) - 1 for b, t in at])
    compared = {k: {"value": own[k], "limit": limits[k]} for k in limits}
    free, _chosen = read()
    witness, chosen = read("bf16")
    info = {"requests_compared": len(picks), "rows_compared": len(at),
            "readings": own,
            "prefill_rows": readings(program_rows[first], want[first]),
            "logit_abs_max": float(np.abs(want).max()),
            "argmax_agree": float(np.mean(program_rows.argmax(-1)
                                          == want.argmax(-1))),
            "free_choice": {
                **readings(program_rows, free),
                "prefill_rows": readings(program_rows[first], free[first]),
                "argmax_agree": float(np.mean(program_rows.argmax(-1)
                                              == free.argmax(-1)))}}
    got, want_w = against(witness, chosen["routes"])
    info["witness_bf16"] = {
        **got, "prefill_rows": readings(witness[first], want_w[first])}
    del witness, free, want_w
    if args.control:
        names = args.control.split(",")
        info["controls"] = {}
        for name in names:
            rows, chosen = read(name)
            got, _want = against(rows, chosen["routes"])
            info["controls"][name] = {
                **got, "not_correct_by": [k for k in limits
                                          if not got[k] <= limits[k]]}
        control = info["controls"][names[0]]
        info.update({"control": args.control,
                     **{"program_" + k: v for k, v in own.items()}})
        for k in compared:
            compared[k]["value"] = control[k]
    info["reference_s"] = time.perf_counter() - t_ref
    return compared, info
