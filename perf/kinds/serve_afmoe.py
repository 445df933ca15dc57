"""Kind ``serve_afmoe``: kind ``serve``'s open loop (``perf/kinds/
serve.py``: the same fleet, gateway and engine, the same offering of
requests, stamps and sweep, imported from there; the device counters
read at the window's two ends as ``serve_hybrid`` reads them) for a
configuration of the ``afmoe`` family, whose cache keeps a ring of the
last window a slot for its window layers beside the paged keys and
values of its full layers, and whose expert layers hold a share of the
experts.

What differs from kind ``serve_qwen3_next``: the program's config is
built from this family's keys (``afmoe_config``; the family's module is
imported here and nowhere the other cells load), and the comparison is
of logits. Once the window's requests are all back and the gateway is
closed, a sample of them is fed again through the timed path's own
programs at the timed sizes (``paged_prefill``, ``paged_install``,
``paged_decode_step`` on the engine's weights and cache), the served
tokens teacher-forced, and the logits the prefill gives and those of
every ``check.every``-th decode step are kept on the host; the program
is freed and ``perf/reference_afmoe.py``'s full forward gives the same
rows.
"""

import gc
import sys
import threading
import time

import numpy as np

from perf import flops, harness, stamps, traffic_gen
from perf import reference_afmoe as reference
from perf.kinds import serve
from perf.kinds.serve_hybrid import _window

from kubeflow_rm_tpu.models import afmoe    # the parent has none: fail fast


def afmoe_config(config: dict):
    """The program's ``AfmoeConfig`` for the configuration file. What
    the file lists as ``assumed`` is held to what the program builds: a
    corrected value there has to come with the code."""
    import jax.numpy as jnp

    built = {"norm": "plain_rms_sandwich", "full_attention_positions": "none",
             "attention_output_gate": "sigmoid_of_normed_input",
             "qk_norm": "rms_a_head", "embedding_scale": "sqrt_hidden_size",
             "shared_expert_gate": "none", "score_func": "sigmoid",
             "route_norm": True, "mup_enabled": True, "hidden_act": "silu",
             "n_group": 1, "topk_group": 1, "num_shared_experts": 1,
             "tie_word_embeddings": False}
    for key, value in built.items():
        if config[key] != value:
            raise ValueError(f"{key} = {config[key]!r}: the program "
                             f"builds {value!r}")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types names a type a layer")
    prec = config["precision"]
    if prec["router"] != "float32":
        raise ValueError("the router is kept in float32")
    return afmoe.AfmoeConfig(
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        vocab_size=config["vocab_size"], dim=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        dense_dim=config["intermediate_size"],
        n_routed_experts=config["router_width"],
        experts_held=(config["experts_held_first"], config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        expert_dim=config["moe_intermediate_size"],
        shared_dim=config["moe_intermediate_size"],
        route_scale=float(config["route_scale"]),
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

    config, mix = cell["config"], cell["traffic"]
    cfg = afmoe_config(config)
    sv = config["serving"]
    d = reference.dims_of(config)
    annotate = jax.profiler.TraceAnnotation

    # ---- set-up ------------------------------------------------------
    make = jax.jit(lambda key: afmoe.init_params(cfg, key))
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
        timer, mark = None, {}
        if tracer:
            at = mix["trace"]["start_frac"] * args.seconds
            timer = threading.Thread(
                target=_trace_slice,
                args=(tracer, t0 + at, mix["trace"]["seconds"], mark),
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
    compared, info = _check(picks, program_rows, at, config, mix, d, args)
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
            "t0": t0, "clock_mark_s": mark.get("clock_mark_s"),
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
        # the programs' own counts over the window: the expert layers'
        # and the keys a layer of each kind attended
        "device_counters": {k: counters[k] for k in counters
                            if k.endswith("_total") and (
                                "expert" in k or "moe_steps" in k
                                or "positions" in k)},
        "window_state_bytes": engine_stats["window_state_bytes"],
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


def _trace_slice(tracer, at, seconds, mark):
    """``serve._trace_slice`` with a mark: once the profiler runs, a span
    ``perf.clock_mark`` entered at ``mark["clock_mark_s"]``
    (``time.perf_counter``), which puts the engine's stamps on the
    trace's host clock (``window_prefill_roofline`` pairs a prefill run
    with its request by it)."""
    import jax
    harness.sleep_until(at)
    tracer.start()
    mark["clock_mark_s"] = time.perf_counter()
    with jax.profiler.TraceAnnotation("perf.clock_mark"):
        pass
    harness.sleep_until(at + seconds)
    tracer.stop()


def _sample(done, requests, records, mix, args):
    """([(prompt, served tokens)] compared, [...] fillers): the longest
    of the requests the window finished and ``check.sample - 1`` others
    drawn from the seed; then the rest of them, in an order drawn from
    the seed, to keep the replay's other slots busy."""
    if not done:
        return [], []
    index = {id(r): i for i, r in enumerate(records)}
    rng = np.random.default_rng(args.seed + 1)
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    others = [r for r in done if r is not longest]
    take = min(len(others), mix["check"]["sample"] - 1)
    chosen = rng.choice(len(others), take, replace=False)
    picks = [longest] + [others[i] for i in chosen]
    rest = [others[i] for i in rng.permutation(len(others))
            if i not in set(chosen.tolist())]

    def pair(r):
        return requests[index[id(r)]]["prompt"], r["tokens"]

    return [pair(r) for r in picks], [pair(r) for r in rest]


def seats(n: int, slots: int) -> list[int]:
    """The slots ``n`` compared requests sit on: spread across the
    engine, the first on slot 0 and the last in its last share."""
    return [b * slots // n for b in range(n)]


def replay(engine, cfg, picks, every: int, fillers=(), live: int = 0):
    """Feed ``picks`` [(prompt, tokens)] through the engine's own
    programs, every slot of the engine's step (the timed shapes), the
    tokens teacher-forced; return the logits rows kept ((n, vocab)
    float32 on the host) and where each sits: ``(row of picks,
    column)``, the column whose next token the row predicts, and
    ``{"seats", "live_mean", "steps"}``: the compared requests' slots
    and the slots a step fed, on average, over the replay's steps. The
    prefill's row of each, then every ``every``-th decode step's and
    each one's last. Pick ``b`` sits on slot ``seats(...)[b]``;
    ``fillers``, in turn, keep ``live`` slots busy in all as the window
    did, each on a slot of its own until its tokens run out, when the
    next takes the slot (a prefill and an install between two steps, as
    the engine admits); their rows are not kept, and the replay ends
    with the last pick. The engine's gateway must be closed: this
    drives its cache alone."""
    import jax
    import jax.numpy as jnp

    from kubeflow_rm_tpu.models import paging
    from kubeflow_rm_tpu.models.generate import _bucket_len

    BS, slots = engine.block_size, engine.slots
    maxb = engine.slot_len // BS
    if len(picks) > slots:
        raise ValueError(f"{len(picks)} requests for {slots} slots")
    cache, rows, at, fed_steps = engine.cache, [], [], []
    seated = {}             # slot -> [tokens, fed, blocks, pick or None]

    def seat(slot, prompt, tokens, b):
        nonlocal cache
        n = len(prompt)
        padded = jnp.asarray([prompt + [0] * (_bucket_len(n) - n)],
                             jnp.int32)
        last, tk, tv, tpos, *state = paging.paged_prefill(
            engine.params, cfg, cache, jnp.zeros((maxb,), jnp.int32),
            jnp.int32(0), padded, jnp.int32(n))
        blocks = engine.pool.alloc(-(-(n + len(tokens)) // BS))
        if blocks is None:
            raise RuntimeError("no blocks free for the replay")
        cache = paging.paged_install(
            cache, tk, tv, tpos, jnp.int32(slot),
            jnp.asarray(blocks + [paging.NULL_BLOCK] * (maxb - len(blocks)),
                        jnp.int32),
            jnp.asarray(blocks + [paging.SINK_BLOCK] * (maxb - len(blocks)),
                        jnp.int32), jnp.int32(n), *state)
        seated[slot] = [tokens, 0, blocks, b]
        if b is not None:
            rows.append(np.asarray(jax.device_get(last), np.float32))
            at.append((b, n - 1))

    mine = seats(len(picks), slots)
    for b, ((prompt, tokens), slot) in enumerate(zip(picks, mine)):
        seat(slot, prompt, tokens, b)
    queue = [f for f in fillers if len(f[1]) > 1]

    def refill():
        for slot in range(slots):
            if len(seated) >= max(live, len(picks)) or not queue:
                return
            if slot not in seated and slot not in mine:
                prompt, tokens = queue.pop(0)
                seat(slot, prompt, tokens, None)

    refill()
    while any(b is not None and fed < len(t) - 1
              for t, fed, _k, b in seated.values()):
        on = [slot in seated and seated[slot][1] < len(seated[slot][0]) - 1
              for slot in range(slots)]
        feed = [seated[slot][0][seated[slot][1]] if on[slot] else 0
                for slot in range(slots)]
        last, cache = paging.paged_decode_step(
            engine.params, cfg, cache, np.asarray(feed, np.int32),
            np.asarray(on))
        fed_steps.append(sum(on))
        keep = []
        for slot in range(slots):
            if not on[slot]:
                continue
            tokens, fed, blocks, b = seated[slot]
            if b is not None and (fed % every == every - 1
                                  or fed == len(tokens) - 2):
                keep.append(slot)
                at.append((b, len(picks[b][0]) + fed))
            seated[slot][1] = fed + 1
            if b is None and fed + 1 == len(tokens) - 1:
                engine.pool.decref(blocks)
                del seated[slot]
        if keep:
            rows.extend(np.asarray(jax.device_get(last[jnp.asarray(keep)]),
                                   np.float32))
        refill()
    engine.cache = cache
    busy = {"seats": mine, "steps": len(fed_steps),
            "live_mean": float(np.mean(fed_steps)) if fed_steps else 0.0}
    return (np.stack(rows) if rows else np.zeros((0, cfg.vocab_size)), at,
            busy)


def row_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """A row's relative error, |got - want| / |want|, (n,) float64."""
    err = np.sum((got.astype(np.float64) - want) ** 2, axis=1)
    return np.sqrt(err / np.sum(want.astype(np.float64) ** 2, axis=1))


def readings(got: np.ndarray, want: np.ndarray) -> dict:
    """What the comparison reads of two sets of logits rows, (n, vocab)
    each: the error's RMS over the reference's, all rows together
    (``logit_err_rms``), and a row's own at the median, the 90th
    percentile and the widest (``logit_err_row_*``)."""
    err = np.sum((got.astype(np.float64) - want) ** 2, axis=1)
    size = np.sum(want.astype(np.float64) ** 2, axis=1)
    rows = row_errors(got, want)
    return {"logit_err_rms": float(np.sqrt(err.sum() / size.sum())),
            "logit_err_row_median": float(np.median(rows)),
            "logit_err_row_p90": float(np.quantile(rows, 0.9)),
            "logit_err_row_max": float(rows.max())}


def _rank(x: np.ndarray) -> np.ndarray:
    return np.argsort(np.argsort(x, kind="stable"), kind="stable")


def routing(own, witness, ref_routes, wit_routes, at, first,
            near: float = 0.3) -> dict:
    """Row by row, how near the float32 reference's choice of held
    experts came to another (``margin``: the least ``_route_of`` lead
    over the expert parts, at the row's column) and whether the
    bfloat16 witness's rounding changed it (``flips``: expert parts
    where the witness's held choice differs); and what the program's and
    the witness's row errors (``own``, ``witness``) read apart on the
    ``near`` share of rows with the least margin (``near_tie``) and the
    rest (``clear``), and on the rows the witness flipped and the rest.
    A rounding can only change a choice whose lead is under it, and the
    program rounds otherwise than the witness, so the program's own
    flips fall among the near ties, not on the witness's rows. ``first``
    marks the prefill rows. ``margin_rank_corr``: the rank correlation
    of a row's error with its margin (negative where the smaller leads
    carry the errors). The twelve rows the program reads worst are
    listed one by one: [request, column, prefill, program, witness,
    flips, margin]."""
    b = np.asarray([p for p, _t in at])
    t = np.asarray([c for _p, c in at])
    flips = sum((np.asarray(r[0])[b, t] != np.asarray(w[0])[b, t])
                for r, w in zip(ref_routes, wit_routes)).astype(int)
    margin = np.min([np.asarray(r[1])[b, t] for r in ref_routes], axis=0)
    flipped = flips > 0
    tie = margin <= np.quantile(margin, near)

    def part(mask):
        if not mask.any():
            return None
        return {"rows": int(mask.sum()),
                "program_median": float(np.median(own[mask])),
                "program_p90": float(np.quantile(own[mask], 0.9)),
                "witness_median": float(np.median(witness[mask])),
                "witness_p90": float(np.quantile(witness[mask], 0.9)),
                "margin_median": float(np.median(margin[mask]))}

    def corr(err):
        a, m = _rank(err), _rank(margin)
        return float(np.corrcoef(a, m)[0, 1]) if len(a) > 1 else None

    tail = own >= np.quantile(own, 0.9)
    worst = np.argsort(-own)[:12]
    return {"near_tie_share": near, "near_tie": part(tie),
            "clear": part(~tie),
            "program_tail_near_tie_share": float(tie[tail].mean()),
            "margin_rank_corr": {"program": corr(own),
                                 "witness": corr(witness)},
            "flipped_share": float(flipped.mean()),
            "flipped": part(flipped), "kept": part(~flipped),
            "program_tail_flipped_share": float(flipped[tail].mean()),
            "prefill_rows_near_tie": int(tie[first].sum()),
            "prefill_rows_flipped": int(flipped[first].sum()),
            "worst_rows": [[int(b[i]), int(t[i]), bool(first[i]),
                            *(float(f"{v:.3g}") for v in (
                                own[i], witness[i])), int(flips[i]),
                            float(f"{margin[i]:.3g}")] for i in worst]}


def _check(picks, program_rows, at, config, mix, d, args):
    """The program's logits rows against the reference's full forward
    over the same teacher-forced columns (the mix's ``check.why`` says
    why each limit is what it is); the widest row, the prefill's rows
    alone and the second witness (the reference at bfloat16, row by row
    against the routing) go to ``info``.

    ``--control`` takes one name or several, comma-separated: the
    reference at eight bits (``int8``) or with one of its planted faults
    (``reference.FAULTS``) in the program's place; the first goes
    through the verdict, every one's readings and the limits it fell by
    go to ``info["controls"]``."""
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
    for b, (prompt, served) in enumerate(picks):
        seq = prompt + served[:-1]
        tokens[b, :len(seq)] = seq
        lengths[b] = len(seq)
    t_ref = time.perf_counter()
    weights = reference.init_weights(
        d, args.seed, jnp.dtype(config["precision"]["params"]))
    x, ref_routes = reference.final_hidden(weights, tokens, d,
                                           lengths=lengths, routes=True)
    want = reference.head_logits(weights, x, d, at)
    del x
    own = readings(program_rows, want)
    first = np.asarray([t == len(picks[b][0]) - 1 for b, t in at])
    compared = {k: {"value": own[k], "limit": limits[k]} for k in limits}
    xw, wit_routes = reference.final_hidden(weights, tokens, d, quant="bf16",
                                            lengths=lengths, routes=True)
    witness = reference.head_logits(weights, xw, d, at, quant="bf16")
    del xw
    info = {"requests_compared": len(picks), "rows_compared": len(at),
            "readings": own,
            "prefill_rows": readings(program_rows[first], want[first]),
            "logit_abs_max": float(np.abs(want).max()),
            "argmax_agree": float(np.mean(program_rows.argmax(-1)
                                          == want.argmax(-1))),
            "witness_bf16": {
                **readings(witness, want),
                "prefill_rows": readings(witness[first], want[first]),
                "argmax_agree": float(np.mean(witness.argmax(-1)
                                              == want.argmax(-1)))},
            "routing": routing(row_errors(program_rows, want),
                               row_errors(witness, want), ref_routes,
                               wit_routes, at, first)}
    del witness
    if args.control:
        names = args.control.split(",")
        info["controls"] = {}
        for name in names:
            low = reference.final_hidden(weights, tokens, d, quant=name,
                                         lengths=lengths)
            read = readings(reference.head_logits(weights, low, d, at,
                                                  quant=name), want)
            del low
            info["controls"][name] = {
                **read, "not_correct_by": [k for k in limits
                                           if not read[k] <= limits[k]]}
        control = info["controls"][names[0]]
        info.update({"control": args.control,
                     **{"program_" + k: v for k, v in own.items()}})
        for k in compared:
            compared[k]["value"] = control[k]
    info["reference_s"] = time.perf_counter() - t_ref
    return compared, info
