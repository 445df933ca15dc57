"""Single-chip training benchmark.

Runs a sharded Llama train step on the TPU jax exposes and prints ONE
JSON line:

    {"metric": "mfu", "value": <percent>, "unit": "%", "vs_baseline": <value/40>,
     "tokens_per_sec": ..., "step_time_ms": ..., ...}

vs_baseline is measured against the BASELINE.json north star of 40% MFU
(the reference itself publishes no numbers — SURVEY.md §6).

No chip, no number: without a TPU the only run allowed is an explicit
``--preset tiny`` (the CI rehearsal of the step and its hostsync
accounting), whose line is labelled ``"metric": "cpu_dry_run"`` and
carries no time, rate or utilisation. Any failure exits non-zero.

Timing discipline: batches stay device-resident, warmup covers compile
+ 2 steps, and the timed region ends in ``block_until_ready`` on the
final step's outputs.
"""

import argparse
import json
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None,
                    help="GLOBAL batch (microbatch = batch / accum); "
                         "with --decode, the decode batch size")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--remat", default=None,
                    help="remat policy (dots/attn/mlp/attn+mlp/full)")
    ap.add_argument("--accum", type=int, default=None,
                    help="gradient-accumulation microbatch count")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--preset", default=None,
                    choices=["tiny", "bench_1b", "bench_2b", "bench_2_7b",
                             "bench_3b", "llama2_7b", "llama2_13b",
                             "llama3_8b"],
                    help="LlamaConfig preset to bench (default: "
                         "bench_1b; without a TPU only an explicit "
                         "tiny runs) — the mfu-vs-scale ladder runs "
                         "bench_1b/bench_2b/bench_3b/llama2_7b")
    ap.add_argument("--optim", choices=["adamw", "adafactor"],
                    default="adamw",
                    help="adafactor = factored second moment, no "
                         "first moment (~0 optimizer bytes/param): "
                         "what fits a ~3B FULL fine-tune on one v5e")
    ap.add_argument("--offload", action="store_true",
                    help="streamed host-offload optimizer step "
                         "(offload='optimizer'): state in host RAM, "
                         "per-leaf updates on host, layer-group chunk "
                         "transfers double-buffered — the MEMPLAN_r01 "
                         "recipe that fits 2.7B full-FT on one v5e. "
                         "On the CPU host with a >tiny preset this "
                         "runs the memplan walk of the real offload "
                         "step instead of executing it")
    ap.add_argument("--artifact", default=None, metavar="PATH",
                    help="with --offload: also write the BENCH_r06 "
                         "artifact (measured row + native offload "
                         "plan + memplan-agreement delta) to PATH")
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="train rank-r adapters on a frozen base "
                         "instead of full fine-tuning (the 7B QLoRA "
                         "recipe)")
    ap.add_argument("--base-quant", choices=["int8", "int4"],
                    default=None,
                    help="with --lora-rank: quantize the frozen base "
                         "(built directly in quantized form on-chip)")
    ap.add_argument("--decode", action="store_true",
                    help="benchmark decode (loop vs fused scan) instead")
    ap.add_argument("--quant", choices=["int8", "int4"], default=None,
                    help="with --decode: weight-only quantize first")
    args = ap.parse_args(argv)
    if args.base_quant and not args.lora_rank:
        ap.error("--base-quant requires --lora-rank (a quantized base "
                 "cannot take full-fine-tune gradients)")
    if args.offload and args.lora_rank:
        ap.error("--offload targets FULL fine-tuning (LoRA state is "
                 "small enough to stay on-chip)")
    if args.decode:
        return decode_bench(args.batch, args.quant, args.preset)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_rm_tpu.models import LlamaConfig
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_rm_tpu.training.train import (
        TrainConfig, init_train_state, make_train_step, shard_batch,
    )
    from kubeflow_rm_tpu.utils.flops import (
        device_peak_flops, train_flops_per_token,
    )

    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"

    if args.offload and not on_tpu and args.preset not in (None, "tiny"):
        # no chip to measure on and a model too big to execute on the
        # CI host: run the memplan walk of the REAL offload step (the
        # same grad-phase jaxpr + stream-slot accounting the step
        # ships) and report the predicted rung — the acceptance gate
        # for the 18.34 -> 13.24 GB drop
        return offload_plan_bench(args.preset, args.artifact)
    if not on_tpu and args.preset != "tiny":
        raise SystemExit(
            f"bench.py: no TPU (jax sees {platform!r}) — a benchmark "
            "number comes from the chip. The only run allowed without "
            "one is an explicit `--preset tiny` rehearsal.")

    if on_tpu:
        # ~1.2B params, bf16 state (~7 G). The defaults (mb2, "dots"
        # remat, accum 64, 1024-block pallas flash fwd+bwd) are the
        # best row of the 2026-07 sweeps, taken on a machine that is
        # gone; they have not been re-measured on today's compiler.
        # Two effects dominated there: grad accumulation amortizes the
        # ~1.2B-param adam update (pure HBM traffic) across K
        # microbatch grads, and "dots" remat beats named-save once the
        # update is off the critical path.
        accum = 64 if args.accum is None else args.accum
        batch = (2 * accum) if args.batch is None else args.batch
        preset = getattr(LlamaConfig, args.preset or "bench_1b")
        model = preset(
            param_dtype=jnp.bfloat16,
            remat_policy=args.remat or "dots",
            **({"max_seq_len": args.seq} if args.seq else {}))
        steps, warmup = args.steps, 2
        seq_len = model.max_seq_len
    else:
        model = LlamaConfig.tiny()
        batch, steps, warmup, accum = 8, args.steps, 2, 1
        if args.batch:
            batch = args.batch
        if args.accum:
            accum = args.accum
        seq_len = 128

    from kubeflow_rm_tpu.training.optim import OptimConfig
    optim = OptimConfig(factored=args.optim == "adafactor",
                        train_only="lora" if args.lora_rank else None,
                        offload="optimizer" if args.offload else "none")
    cfg = TrainConfig(model=model, optim=optim)
    mesh = make_mesh(MeshConfig(dp=1, fsdp=1, sp=1, tp=1),
                     devices=devices[:1])

    if args.lora_rank:
        from kubeflow_rm_tpu.models import add_lora, init_params
        if args.base_quant:
            from kubeflow_rm_tpu.models.quantize import (
                init_params_quantized,
            )
            params = init_params_quantized(
                model, jax.random.key(0),
                bits=4 if args.base_quant == "int4" else 8)
        else:
            params = init_params(model, jax.random.key(0))
        params = add_lora(params, args.lora_rank, key=jax.random.key(1))
        state = init_train_state(cfg, jax.random.key(0), params=params)
    else:
        state = init_train_state(cfg, jax.random.key(0))
    step = make_train_step(cfg, mesh, state, grad_accum=accum)

    rng = np.random.default_rng(0)
    tok = rng.integers(0, model.vocab_size, (batch, seq_len), dtype=np.int32)
    labels = np.roll(tok, -1, axis=1).astype(np.int32)
    host_batch = {"tokens": tok, "labels": labels}
    dev_batch = shard_batch(host_batch, mesh)  # device-resident once

    # hostsync probe (no-op unless KFRM_HOSTSYNC_PROBE=1): every step
    # runs inside a hot region, so the offload arm's streaming is only
    # clean because it is sanctioned — any OTHER implicit sync in the
    # step shows up in "unsanctioned_syncs" and fails the CI gate
    from kubeflow_rm_tpu.analysis.jaxcheck import hostsync
    hostsync.install()

    for _ in range(warmup):
        with hostsync.region("bench.step"):
            state, metrics = step(state, dev_batch)
    jax.block_until_ready((state, metrics))

    t0 = time.perf_counter()
    for _ in range(steps):
        with hostsync.region("bench.step"):
            state, metrics = step(state, dev_batch)
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    out = {
        "device": {"platform": platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
        "model": f"llama-{args.preset or 'bench_1b'}",
        "batch": batch,
        "grad_accum": accum,
        "seq_len": seq_len,
        "remat_policy": model.remat_policy,
        "optim": args.optim,
        "steps": steps,
        "final_loss": round(final_loss, 4),
    }
    if on_tpu:
        step_time = dt / steps
        tokens_per_sec = batch * seq_len / step_time
        flops_tok = train_flops_per_token(
            model, seq_len, frozen_base=bool(args.lora_rank))
        peak = device_peak_flops(devices[0])
        mfu_pct = 100.0 * tokens_per_sec * flops_tok / peak
        out = {
            "metric": "mfu",
            "value": round(mfu_pct, 2),
            "unit": "%",
            "vs_baseline": round(mfu_pct / 40.0, 4),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "step_time_ms": round(step_time * 1e3, 2),
            "achieved_tflops": round(
                tokens_per_sec * flops_tok / 1e12, 2),
            **out,
        }
        if args.offload:
            out["offload_transfer_ms"] = round(
                float(metrics.get("offload_transfer_ms", 0.0)), 3)
            out["offload_overlap_frac"] = round(
                float(metrics.get("offload_overlap_frac", 0.0)), 3)
        if args.lora_rank:
            # honest accounting: frozen-base training executes ~4
            # FLOPs/param/token, and that is what "value" charges; the
            # 6N full-fine-tune convention is carried alongside
            six_n = tokens_per_sec * train_flops_per_token(model, seq_len)
            out["mfu_6n_convention"] = round(100.0 * six_n / peak, 2)
    else:
        # a CPU run proves the step runs and counts its syncs; it has
        # no time, rate or utilisation to report
        out = {"metric": "cpu_dry_run", **out}
    if args.offload:
        out["offload"] = "optimizer"
    if hostsync.enabled():
        out["unsanctioned_syncs"] = len(hostsync.witnesses())
        out["sanctioned_syncs"] = sum(hostsync.sanctioned_counts()
                                      .values())
    if args.lora_rank:
        out["lora_rank"] = args.lora_rank
        out["base_quant"] = args.base_quant or "bf16"
    if args.offload and args.artifact:
        write_offload_artifact(args.artifact, out)
    print(json.dumps(out))


def _priced_offload_rows():
    """MEMPLAN_r01's priced host-offload extrapolation — read from the
    checked-in artifact when present (repo root), else the published
    figures, so the agreement delta always has a reference."""
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MEMPLAN_r01.json")
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)["extrapolation"]["host_offload"]
    except (OSError, KeyError, ValueError):
        return [{"name": "2.7B (priced)", "on_chip_peak_gb": 13.24,
                 "fit": True},
                {"name": "7B (priced)", "on_chip_peak_gb": 30.41,
                 "fit": False}]


def _offload_agreement(native):
    priced = _priced_offload_rows()
    rows = []
    for p, n in zip(priced, native):
        delta = (100.0 * (n["on_chip_peak_gb"] - p["on_chip_peak_gb"])
                 / p["on_chip_peak_gb"])
        rows.append({"preset": n["preset"],
                     "priced_on_chip_peak_gb": p["on_chip_peak_gb"],
                     "native_on_chip_peak_gb": n["on_chip_peak_gb"],
                     "delta_pct": round(delta, 1),
                     "verdicts_match": p["fit"] == n["fit"]})
    # bridge the drift into the TSDB-sampled registry: sustained >20%
    # trips the warn-only declared-hbm-drift SLO at /api/alerts
    from kubeflow_rm_tpu.controlplane.webhook.admission_pricer import (
        record_declared_drift,
    )
    record_declared_drift(rows)
    return rows


def write_offload_artifact(path, measured_row) -> dict:
    """Compose and write BENCH_r06: the measured offload row (tiny on
    the CI host, the real rung on a chip), the native memplan walk of
    the shipped offload step, and the agreement delta against
    MEMPLAN_r01's priced 13.24 GB extrapolation."""
    from kubeflow_rm_tpu.analysis.jaxcheck.memplan import (
        USABLE_GIB, offload_native_rows,
    )
    native = offload_native_rows()
    artifact = {
        "artifact": "BENCH_r06",
        "generated_by": "python bench.py --preset tiny --offload "
                        "--artifact BENCH_r06.json "
                        "(KFRM_HOSTSYNC_PROBE=1 in CI)",
        "summary": "streamed host-offload optimizer step, shipped: "
                   "the 2.7B full-FT rung the chip OOMs at 18.34 GB "
                   "today is predicted to fit on-chip by the walk of "
                   "the REAL step, within the band MEMPLAN_r01 "
                   "priced before the code existed",
        "usable_gib": USABLE_GIB,
        "measured": measured_row,
        "offload_plan": native,
        "memplan_agreement": _offload_agreement(native),
    }
    with open(path, "w", encoding="utf-8") as f:
        f.write(json.dumps(artifact, indent=1) + "\n")
    return artifact


def offload_plan_bench(preset, artifact=None) -> None:
    """``--offload`` with a >tiny preset on the CPU host: no chip to
    measure, so walk the REAL offload step for the ladder and report
    the requested preset's predicted rung as the metric line."""
    from kubeflow_rm_tpu.analysis.jaxcheck.memplan import (
        USABLE_GIB, offload_native_rows,
    )
    native = offload_native_rows()
    agreement = _offload_agreement(native)
    row = next((r for r in native if r["preset"] == preset), native[0])
    out = {
        "metric": "offload_plan_peak_gb",
        "value": row["on_chip_peak_gb"],
        "unit": "GB",
        # the drop that matters: predicted on-chip peak vs the 15.75
        # GiB usable budget (the no-offload 2.7B walk says 18.34)
        "vs_baseline": round(row["on_chip_peak_gb"]
                             / (USABLE_GIB * (2 ** 30) / 1e9), 4),
        "fit": row["fit"],
        "preset": preset,
        "grad_phase_peak_gb": row["grad_phase_peak_gb"],
        "stream_slot_gb": row["stream_slot_gb"],
        "offload": "optimizer",
        "memplan_agreement": agreement,
    }
    if artifact:
        write_offload_artifact(artifact, out)
    print(json.dumps(out))


def decode_bench(batch=None, quant=None, preset=None) -> None:
    """Loop-vs-fused decode throughput (``--decode``): the per-token
    jit dispatch of ``generate`` against the single-program
    ``generate_fused`` scan, same bf16 bench-1b weights and cache.
    ``--batch`` scales the decode batch (HBM-bandwidth-bound: tokens/s
    should rise nearly linearly until the cache+weights saturate)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_rm_tpu.models import (
        LlamaConfig, generate, generate_fused, init_params,
    )

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py --decode: no TPU (jax sees "
            f"{devices[0].platform!r}) — every number it prints is a "
            "device time")
    make = getattr(LlamaConfig, preset or "bench_1b")
    cfg = make(param_dtype=jnp.bfloat16)
    B, Tp, new = batch or 4, 128, 384
    if quant:
        # build DIRECTLY in quantized form: a 7B never has a resident
        # full-precision copy on a 16 GiB chip
        from kubeflow_rm_tpu.models.quantize import init_params_quantized
        params = init_params_quantized(
            cfg, jax.random.key(0), bits=4 if quant == "int4" else 8)
    else:
        params = init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (B, Tp), 0,
                                cfg.vocab_size)

    def timed(fn):
        jax.block_until_ready(fn())     # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return time.perf_counter() - t0

    t_loop = timed(lambda: generate(
        params, cfg, prompt, max_new_tokens=new))
    t_fused = timed(lambda: generate_fused(
        params, cfg, prompt, max_new_tokens=new))
    print(json.dumps({
        "metric": "decode_tokens_per_sec",
        "value": round(B * new / t_fused, 1),
        "unit": "tok/s",
        "vs_baseline": round(t_loop / t_fused, 2),
        "batch": B, "prefill": Tp, "new_tokens": new,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices)},
        "model": f"llama-{preset or 'bench_1b'}",
        "loop_ms_per_token": round(1e3 * t_loop / new, 2),
        "fused_ms_per_token": round(1e3 * t_fused / new, 2),
        "speedup": round(t_loop / t_fused, 2),
        **({"quant": quant} if quant else {}),
    }))


if __name__ == "__main__":
    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
