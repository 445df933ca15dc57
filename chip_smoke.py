"""chip_smoke.py — does the system still start on the chip?

Drives the repo's two main paths once each, through the entry points a
tenant calls, at the full width of ``LlamaConfig.bench_1b`` (bf16
params, random weights from a seed), in ONE process that owns the chip:

- **serve**: ``ContinuousBatchingEngine`` ->
  ``ServingGateway`` -> one-replica ``ServingFleet`` ->
  ``submit_and_wait`` for nine concurrent requests over three prefill
  buckets, one pair sharing a prefix (block adoption) and one exact
  repeat (copy-on-write fork). Checked in the run: every request gets
  its full token budget, the prefix cache hit, a paged prefill's
  last-token logits agree with plain ``models.forward``, every
  generated token, teacher-forced through ``forward``, sits within
  rounding of the reference argmax, and the lowered decode step holds
  the paged-attention pallas kernel. Whether each output is
  token-identical to solo ``generate_fused`` is reported, not gated:
  on the chip bf16 argmax at random init flips within a few tokens.
- **train**: ``training.loop.fit()`` on a one-device mesh over packed
  documents (segment-masked pallas flash forward and backward),
  microbatch 1 x grad_accum 4 at seq 2048 with "full" remat — the
  compile-time memory analysis puts that at 14.5 GB against 17.4 GB for
  "dots", on a chip with 15.75 GiB usable. Checked: loss and grad norm
  finite, loss lower at the last step than the first on a repeated
  batch, and the lowered step contains the pallas kernel.

Run with no arguments it requires a TPU and fails before doing any work
without one. ``--cpu-dry-run`` is the rehearsal, never a fallback: the
same code at ``LlamaConfig.tiny()`` on the CPU with the pallas kernels
in interpret mode, its output labelled ``"device": "cpu"``.

The last line of stdout is one JSON object with exactly two keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as jax reports it; the line before it, ``summary: {...}``,
carries everything the run measured. Any failed phase means a non-zero
exit and neither line. Wall times are printed as information only: this
script records no speed.
"""

import argparse
import contextlib
import dataclasses
import faulthandler
import gc
import itertools
import json
import logging
import os
import sys
import threading
import time
import traceback

#: whole-run watchdog: dump every thread's stack and exit non-zero
#: rather than hang the chip past the driver's 1200 s limit
DEADLINE_S = 1150


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""
    slots: int
    slot_len: int
    block_size: int
    prompt_lens: tuple     # one per prefill bucket, ascending
    new_tokens: tuple      # token budget for each of ``prompt_lens``
    shared_prefix: int     # block-aligned; the pair below shares it
    pair_len: int          # length of the prefix-sharing prompts
    seq: int               # training sequence length
    steps: int
    logit_tol: float       # max |paged - forward| / max |forward|
    request_timeout_s: float


CHIP = Sizes(slots=8, slot_len=1024, block_size=16,
             prompt_lens=(100, 200, 500), new_tokens=(32, 48, 64),
             shared_prefix=272, pair_len=300, seq=2048, steps=5,
             # bf16 carries 8 mantissa bits (2^-8 = 0.4% per rounding)
             # and the two programs round differently through 20
             # layers (XLA scores vs the flash kernel's online
             # softmax): a random walk of ~120 roundings is ~4%. The
             # chip measured 2.2%; a wrong mask or block table is O(1).
             logit_tol=5e-2, request_timeout_s=900.0)
DRY = Sizes(slots=4, slot_len=64, block_size=4,
            prompt_lens=(6, 12, 28), new_tokens=(4, 6, 8),
            shared_prefix=16, pair_len=20, seq=128, steps=3, logit_tol=1e-4, request_timeout_s=120.0)

GRAD_ACCUM = 4
REMAT = "full"     # 14.5 GB by the compile-time analysis; "dots" reads 17.4
SEED = 0


class _AttentionLog(logging.Handler):
    """Collect ``ops.attention``'s kernel decisions for the report."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.choices = []

    def emit(self, record):
        kernel, interpret, platform, n_devices, why = record.args
        self.choices.append({"kernel": kernel, "interpret": interpret,
                             "platform": platform, "devices": n_devices,
                             "why": why})

    def take(self):
        out = [dict(t) for t in {tuple(c.items()) for c in self.choices}]
        self.choices = []
        return sorted(out, key=lambda c: (c["kernel"], c["why"]))


class _CompileClock:
    """Seconds jax spent getting executables (compiling, or loading
    them from the persistent cache) — what collapses on a warm run.
    Compiles land from the main and the drain thread; ``take`` runs
    between phases, when neither is compiling."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self._event = BACKEND_COMPILE_EVENT
        self._durations = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self._event:
            self._durations.append(duration)

    def take(self):
        taken, self._durations = self._durations, []
        return {"compile_s": round(sum(taken), 2), "programs": len(taken)}

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def _memory(device) -> dict | None:
    stats = device.memory_stats()
    if not stats:
        return None     # the CPU backend reports none
    return {"bytes_in_use": stats["bytes_in_use"],
            "peak_bytes_in_use": stats["peak_bytes_in_use"]}


@contextlib.contextmanager
def _kernels_interpreted():
    """Rehearsal only: send the model's attention calls — training's
    and the paged decode step's — to the pallas kernels (which
    interpret off-TPU) instead of the XLA paths "auto" picks on a CPU,
    so the CPU run walks the code the chip will."""
    from functools import partial
    from unittest import mock

    from kubeflow_rm_tpu.models import llama, paging
    from kubeflow_rm_tpu.ops import dot_product_attention
    from kubeflow_rm_tpu.ops.paged_attention import paged_decode_attention
    with mock.patch.object(llama, "dot_product_attention",
                           partial(dot_product_attention, impl="flash")), \
            mock.patch.object(paging, "paged_decode_attention",
                              partial(paged_decode_attention,
                                      impl="pallas")):
        yield


# ---------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------

def _requests(sz: Sizes, vocab: int) -> list[tuple[list[int], int]]:
    """(prompt, max_new_tokens) pairs from the seed: two prompts per
    bucket length, then the prefix pair and an exact repeat of its
    first member."""
    import numpy as np
    rng = np.random.default_rng(SEED)

    def toks(n):
        return [int(t) for t in rng.integers(1, vocab, n)]

    out = []
    for n, new in zip(sz.prompt_lens, sz.new_tokens):
        out += [(toks(n), new), (toks(n), new)]
    prefix = toks(sz.shared_prefix)
    tail = sz.pair_len - sz.shared_prefix
    new = sz.new_tokens[-1]
    first = prefix + toks(tail)
    out += [(first, new), (prefix + toks(tail), new), (list(first), new)]
    return out


def serve_phase(cfg, sz: Sizes, device) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_rm_tpu.controlplane.serving_fleet import ServingFleet
    from kubeflow_rm_tpu.controlplane.webapps.serving import (
        ServingGateway, TenantPolicy,
    )
    from kubeflow_rm_tpu.models import forward, generate_fused, init_params
    from kubeflow_rm_tpu.models.generate import (
        ContinuousBatchingEngine, _bucket_len,
    )

    # the references below read the tree as trained; the engine shares
    # its leaves but for the projections it keeps (out, in)
    params = init_params(cfg, jax.random.key(SEED))
    engine = ContinuousBatchingEngine(
        params, cfg, slots=sz.slots, slot_len=sz.slot_len,
        block_size=sz.block_size)
    # the tenant's latency promise has to admit a cold start: the
    # first requests wait out every compile, and the default 2 s SLO
    # would shed whoever submits after them
    policy = TenantPolicy(slo_p95_ms=1e3 * sz.request_timeout_s)
    fleet = ServingFleet(
        {"r0": ServingGateway(engine, policies={"smoke": policy})})
    requests = _requests(sz, cfg.vocab_size)
    results: list = [None] * len(requests)

    # the decode step the engine will dispatch, lowered: on the chip
    # it must read the pool through the pallas kernel, not fall to the
    # XLA gather (nothing runs here, so the cache is not donated yet)
    from kubeflow_rm_tpu.models import paging
    kernels = paging.paged_decode_step.lower(
        engine.params, cfg, engine.cache, jnp.zeros((sz.slots,), jnp.int32),
        jnp.zeros((sz.slots,), bool)).as_text().count("tpu_custom_call")
    if device.platform == "tpu" and not kernels:
        raise RuntimeError("the lowered paged_decode_step holds no "
                           "pallas kernel: decode attention fell to "
                           "the XLA path")

    def client(i):
        prompt, new = requests[i]
        try:
            results[i] = fleet.submit_and_wait(
                "smoke", prompt, max_new_tokens=new,
                timeout_s=sz.request_timeout_s)
        except BaseException as e:   # re-raised on the main thread
            results[i] = e

    t0 = time.perf_counter()
    try:
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(sz.request_timeout_s + 30)
            if t.is_alive():
                raise TimeoutError("a serving request never returned")
        wall = time.perf_counter() - t0

        outputs, timelines = [], []
        for (prompt, new), res in zip(requests, results):
            if isinstance(res, BaseException):
                raise res
            tokens, info = res
            if tokens is None:
                raise RuntimeError(f"request shed: {info}")
            if len(tokens) != new:
                raise RuntimeError(
                    f"request returned {len(tokens)} of {new} tokens")
            outputs.append(tokens)
            timelines.append(info["timeline"])

        stats = engine.stats()
        if stats["prefix_hit_tokens"] <= 0:
            raise RuntimeError(f"no prefix-cache hit: {stats}")
        if stats["cow_forks"] < 1:
            raise RuntimeError(f"no copy-on-write fork: {stats}")

        # the reference for both checks below: plain forward() over one
        # right-padded row of slot_len tokens (causal: the pad is
        # inert; one shape, one compile, a length the kernel tiles)
        run_forward = jax.jit(forward, static_argnames=("cfg",))

        def reference(tokens):
            row = tokens + [0] * (sz.slot_len - len(tokens))
            return run_forward(params, jnp.asarray([row], jnp.int32),
                               cfg=cfg)[0]               # (slot_len, V)

        # one paged prefill against forward() on the same tokens, a
        # prompt the cache has not seen
        n = sz.prompt_lens[1]
        probe = [int(t) for t in np.random.default_rng(SEED + 1).integers(
            1, cfg.vocab_size, n)]
        chain = fleet.gateways["r0"].prefill_chain(probe)
        if chain is None:
            raise RuntimeError("prefill_chain refused the probe prompt")
        paged = np.asarray(chain["last_logits"], np.float32)
        ref = np.asarray(reference(probe)[n - 1])
        if not (np.isfinite(paged).all() and np.isfinite(ref).all()):
            raise RuntimeError("non-finite logits")
        err = float(np.abs(paged - ref).max())
        scale = float(np.abs(ref).max())
        if err > sz.logit_tol * scale:
            raise RuntimeError(
                f"paged prefill vs forward: max|diff| {err:.4g} > "
                f"{sz.logit_tol} x max|ref| {scale:.4g}")

        # every generated token, teacher-forced through forward(): the
        # token the engine chose may trail the reference's own best
        # logit by rounding on either side, never by more. This is
        # what tells a broken decode step from bf16 argmax flips, which
        # the token comparison below cannot.
        @jax.jit
        def trailing(logits, chosen):   # (T, V); (T,) token or -1
            picked = jnp.take_along_axis(
                logits, jnp.maximum(chosen, 0)[:, None], axis=-1)[:, 0]
            return jnp.where(chosen >= 0, logits.max(-1) - picked,
                             0.0).max()

        gaps = []
        for (prompt, new), got in zip(requests, outputs):
            chosen = np.full(sz.slot_len, -1, np.int32)
            chosen[len(prompt) - 1:len(prompt) - 1 + new] = got
            gaps.append(trailing(reference(prompt + got[:-1]), chosen))
        worst_gap = float(max(jax.device_get(gaps)))
        if not worst_gap <= 2 * sz.logit_tol * scale:
            raise RuntimeError(
                f"a generated token trails the reference argmax by "
                f"{worst_gap:.4g} > 2 x {sz.logit_tol} x {scale:.4g}")

        # reported, not gated: the engine's exactness contract on
        # today's device
        identical, first_diff = 0, []
        for (prompt, new), got in zip(requests, outputs):
            solo = generate_fused(
                params, cfg, jnp.asarray([prompt], jnp.int32),
                max_new_tokens=new, max_len=sz.slot_len)
            want = [int(t) for t in np.asarray(solo)[0, len(prompt):]]
            diff = next((j for j, (a, b) in enumerate(zip(got, want))
                         if a != b), None)
            identical += diff is None
            first_diff.append(diff)
    finally:
        fleet.close()

    return {
        "requests": len(requests),
        "tokens_returned": sum(len(o) for o in outputs),
        "prefill_buckets": sorted({_bucket_len(len(p))
                                   for p, _ in requests}),
        "prefills": stats["prefills"],
        "decode_steps": stats["decode_steps"],
        "kv_blocks_read_total": stats["kv_blocks_read_total"],
        # a pick program and a blocking transfer a token boundary,
        # whatever the live slots (PR 37; occupancy_sum before it)
        "host_syncs_total": stats["host_syncs_total"],
        "pick_programs_total": stats["pick_programs_total"],
        "pallas_kernels_in_lowered_decode_step": kernels,
        "batch_occupancy": round(stats["batch_occupancy"], 3),
        "prefix_hit_tokens": stats["prefix_hit_tokens"],
        "cow_forks": stats["cow_forks"],
        "logits_max_abs_err": round(err, 5),
        "logits_max_abs_ref": round(scale, 4),
        "logits_tolerance": sz.logit_tol,
        "worst_teacher_forced_gap": round(worst_gap, 5),
        "token_identical_to_generate_fused": f"{identical}/{len(requests)}",
        "first_mismatch_at": first_diff,
        "wall_s_informational": round(wall, 2),
        **_timeline_summary(timelines),
    }


def _timeline_summary(timelines: list) -> dict:
    """Median and 95th percentile, in ms, of what the engine's stamps
    say of the requests: the wait for a slot, the time to the first
    token and the gap between tokens. On a cold start the first two
    hold the compiles. Reported, not gated."""
    import numpy as np

    series = {
        "queue_wait": [t["t_admitted"] - t["t_submitted"]
                       for t in timelines],
        "time_to_first_token": [t["t_first_token"] - t["t_submitted"]
                                for t in timelines],
        "inter_token": [d for t in timelines
                        for d in np.diff(t["t_tokens"])],
    }
    return {f"{name}_ms_informational": {
                "p50": round(1e3 * float(np.percentile(v, 50)), 2),
                "p95": round(1e3 * float(np.percentile(v, 95)), 2)}
            for name, v in series.items()}


# ---------------------------------------------------------------------
# train
# ---------------------------------------------------------------------

def _packed_batch(sz: Sizes, vocab: int) -> dict:
    """GRAD_ACCUM rows (microbatch 1) of packed seeded documents."""
    import numpy as np

    from kubeflow_rm_tpu.training.data import pack_documents
    rng = np.random.default_rng(SEED)
    docs, total = [], 0
    while total < (GRAD_ACCUM + 1) * sz.seq:
        n = int(rng.integers(sz.seq // 8, sz.seq // 2))
        docs.append([int(t) for t in rng.integers(1, vocab, n)])
        total += n
    packed = pack_documents(docs, sz.seq)
    return {k: v[:GRAD_ACCUM] for k, v in packed.items()}


def train_phase(cfg, sz: Sizes, device) -> dict:
    import jax
    import numpy as np

    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_rm_tpu.training.loop import LoopConfig, fit
    from kubeflow_rm_tpu.training.optim import OptimConfig
    from kubeflow_rm_tpu.training.train import (
        TrainConfig, init_train_state, make_train_step,
    )

    mesh = make_mesh(MeshConfig(fsdp=1), devices=[device])
    tc = TrainConfig(
        model=dataclasses.replace(cfg, remat_policy=REMAT),
        # one warm-up step, then the full rate: bf16 weights do not
        # register the default schedule's first few micro-updates
        optim=OptimConfig(warmup_steps=1, total_steps=sz.steps))
    batch = _packed_batch(sz, cfg.vocab_size)
    if int(batch["segments"].max()) < 2:
        raise RuntimeError("packed batch holds a single document")

    # the step fit() will build, lowered: on the chip it must carry
    # the pallas kernels, not the XLA attention path
    shapes = jax.eval_shape(
        lambda: init_train_state(tc, jax.random.key(SEED)))
    lowered = make_train_step(
        tc, mesh, shapes, batch_keys=tuple(batch),
        grad_accum=GRAD_ACCUM).lower(
            shapes, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in batch.items()})
    kernels = lowered.as_text().count("tpu_custom_call")
    if device.platform == "tpu" and not kernels:
        raise RuntimeError("the lowered train step holds no pallas "
                           "kernel: attention fell to the XLA path")

    t0 = time.perf_counter()
    state, history = fit(
        tc, mesh, itertools.repeat(batch),
        LoopConfig(total_steps=sz.steps, log_every=1, seed=SEED,
                   grad_accum=GRAD_ACCUM))
    wall = time.perf_counter() - t0
    losses = [h.loss for h in history]
    norms = [h.grad_norm for h in history]
    if len(history) != sz.steps or int(state.step) != sz.steps:
        raise RuntimeError(f"took {len(history)} of {sz.steps} steps")
    if not (np.isfinite(losses).all() and np.isfinite(norms).all()):
        raise RuntimeError(f"non-finite loss/grad norm: {losses} {norms}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a repeated batch: "
                           f"{losses}")
    return {
        "steps": sz.steps, "grad_accum": GRAD_ACCUM, "microbatch": 1,
        "seq": sz.seq, "remat": REMAT,
        "documents_in_batch": int(batch["segments"].max()),
        "pallas_kernels_in_lowered_step": kernels,
        "loss": [round(x, 4) for x in losses],
        "grad_norm": [round(x, 4) for x in norms],
        "wall_s_informational": round(wall, 2),
    }


# ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="rehearse at LlamaConfig.tiny() on the CPU "
                         "(never the default, never inferred)")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    device = devices[0]
    if device.platform != ("cpu" if dry else "tpu"):
        # with JAX_PLATFORMS unset jax carries on on the CPU when
        # another process holds the chip; that is a failure here
        print(f"chip_smoke: need a {'cpu' if dry else 'tpu'}, jax found "
              f"{device.platform!r} ({device.device_kind})",
              file=sys.stderr)
        return 1
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True,
                                      file=sys.__stderr__)

    import importlib.metadata as md

    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    device_json = {"platform": device.platform,
                   "kind": device.device_kind, "count": len(devices)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu}
    print(f"device {device_json} versions {versions}")
    print(f"compile cache: {cache_dir}")

    from kubeflow_rm_tpu.models import LlamaConfig
    sz = DRY if dry else CHIP
    cfg = (LlamaConfig.tiny() if dry
           else LlamaConfig.bench_1b(param_dtype=jnp.bfloat16))

    attention = _AttentionLog()
    attn_logger = logging.getLogger("kubeflow_rm_tpu.ops.attention")
    old_level = attn_logger.level
    attn_logger.addHandler(attention)
    attn_logger.setLevel(logging.INFO)
    clock = _CompileClock()

    report, failed = {}, []
    try:
        with _kernels_interpreted() if dry else contextlib.nullcontext():
            for name, phase in (("serve", serve_phase),
                                ("train", train_phase)):
                t0 = time.perf_counter()
                try:
                    out = phase(cfg, sz, device)
                except Exception:
                    failed.append(name)
                    print(f"--- {name} phase FAILED", file=sys.stderr)
                    traceback.print_exc()
                    out = {}
                gc.collect()    # the phase's arrays go before the next
                out.update(clock.take())
                out["attention"] = attention.take()
                out["memory"] = _memory(device)
                out["phase_wall_s_informational"] = round(
                    time.perf_counter() - t0, 2)
                report[name] = out
                print(f"{name}: {json.dumps(out)}")
    finally:
        clock.close()
        attn_logger.removeHandler(attention)
        attn_logger.setLevel(old_level)
        faulthandler.cancel_dump_traceback_later()

    interpreted = [c for p in report.values() for c in p["attention"]
                   if c["interpret"]]
    if not dry and interpreted:
        failed.append("interpret")
        print(f"--- pallas ran interpreted on the chip: {interpreted}",
              file=sys.stderr)
    for phase, kernel in (("train", "flash"), ("serve", "paged_decode")):
        if dry and not failed and not any(
                c["kernel"] == kernel
                for c in report[phase]["attention"]):
            failed.append("rehearsal")
            print(f"--- the rehearsal's {phase} phase never reached "
                  f"the {kernel} kernel", file=sys.stderr)
    if failed:
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    print("summary: " + json.dumps({
        "device": "cpu" if dry else device_json,
        "versions": versions, "compile_cache": cache_dir,
        "model": "tiny" if dry else "bench_1b",
        "serve": report["serve"], "train": report["train"],
        "claim": None,
    }))
    # the driver's contract: exactly these keys, nothing after this line
    print(json.dumps({"ok": True, "device": device_json}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
