#!/usr/bin/env python3
"""Serve a Llama from the slice this notebook was spawned with.

The inference-side counterpart of ``finetune_llama.py``: a small HTTP
server around the single-program decode path (``generate_fused`` /
``make_generate_step``), meant to run inside a jupyter-jax notebook or
as the command of a spawned serving pod. The reference platform ships
no model runtime at all (SURVEY.md §2.6) — serving is capability the
TPU image adds on top.

TPU-shaped choices:

- **Micro-batching.** Requests arriving within a batching window are
  padded into one fixed-shape ``generate_fused`` call — decode is
  HBM-bandwidth-bound, so tokens/sec scales nearly free with batch.
- **Shape buckets.** Prompts pad up to power-of-two buckets and
  ``max_new_tokens`` is server-fixed, so XLA compiles a handful of
  programs once instead of one per request shape.
- **Token ids in/out.** The API speaks token ids (JSON lists);
  tokenization happens client-side (or pass ``--hf-tokenizer`` to
  decode text server-side when the files are available).

API: ``POST /generate {"prompt": [ids...], "temperature"?: t,
"top_k"?: k}`` → ``{"tokens": [ids...]}``; ``GET /healthz``.
Generation length is server-fixed (``--max-new-tokens``); sampling
params are compile-shape keys, so temperature snaps to a 0.05 grid
and top_k snaps to a small allowed set — both documented below.

Speculative decode — where it lives and what gates it:

| Surface | Knob | Gate |
|---|---|---|
| this server | ``--speculative`` (process-wide) | solo greedy batch-1 requests only; batched/sampled requests fall back to plain fused decode |
| engine / gateway | ``POST /generate {"speculative": true}`` per request | ``slo_class`` must be ``batch`` or ``best_effort`` (interactive keeps the paged continuous-batching path), greedy only, prompt > 3 tokens |
| fleet front door | same per-request field, any replica | disaggregated fleets run it decode-side and skip prefix staging (the drafter needs the whole prompt locally) |

All three run ``generate_speculative_fused`` (prompt-lookup n-gram
drafting + one fused verify pass per round) and are exactness-
preserving: output is token-for-token what plain greedy decode
produces, never an approximation — wins show up as fewer model calls
on repetitive continuations, worst case is one extra verify call.

Tiny smoke (CPU, what tests/test_examples.py runs):
    python examples/serve_llama.py --preset tiny --selftest
Real chip:
    python examples/serve_llama.py --preset llama2_7b \
        --hf-model meta-llama/Llama-2-7b-hf --int8 --port 8000
"""

from __future__ import annotations

import argparse
import json
import queue
import sys
import threading
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


# top_k values the API serves; requests snap to the nearest member
# (top_k is a static compile key — see make_app)
TOP_K_CHOICES = (1, 5, 10, 20, 50, 100)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class Batcher:
    """Collects concurrent generate requests into fixed-shape batches.

    One background thread drains the queue: it waits for the first
    request, then up to ``window_ms`` for stragglers (bounded by
    ``max_batch``), pads all prompts (left-pad with ``pad_id``, which
    doubles as a "begin" token) into the smallest power-of-two bucket,
    and runs ONE fused generation for the whole batch. Each waiter
    gets its row back, trimmed of padding.
    """

    def __init__(self, step_fn, *, max_new_tokens: int, pad_id: int = 0,
                 window_ms: float = 5.0, max_batch: int = 8,
                 rows_multiple: int = 1, exact_solo: bool = False):
        # step_fn: (ids (B,T), pad_counts (B,), temperature, top_k)
        #          -> (B, T+new)
        self.step_fn = step_fn
        self.max_new_tokens = max_new_tokens
        self.pad_id = pad_id
        self.window_ms = window_ms
        self.max_batch = max_batch
        # sharded batches must divide the mesh's data axes: dummy rows
        # (copies of row 0) round B up, and only real rows are returned
        self.rows_multiple = rows_multiple
        # speculative solo requests need the exact prompt (no pads) —
        # costs one compile per distinct prompt length instead of per
        # bucket, the price of the lookup decoder's prefix semantics.
        # The length set is capped: beyond it, solo requests fall back
        # to bucketing so cycling lengths can't accumulate compiles.
        self.exact_solo = exact_solo
        self._exact_lens: set = set()
        self.q: queue.Queue = queue.Queue()
        self.batches_run = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def submit(self, prompt: list[int], temperature: float = 0.0,
               top_k: int | None = None) -> list[int]:
        """Blocking: returns prompt + continuation token ids."""
        if self._stop.is_set():
            raise RuntimeError("batcher is closed")
        done = threading.Event()
        box: dict = {"prompt": prompt, "temperature": temperature,
                     "top_k": top_k, "done": done}
        self.q.put(box)
        # wake periodically: if close() killed the drain thread while
        # this request sat queued, nobody will ever set done — an
        # in-flight batch still completes (the thread finishes its
        # current batch before exiting), so only stop+dead-thread is
        # a guaranteed-orphan condition
        while not done.wait(timeout=1.0):
            if self._stop.is_set() and not self._thread.is_alive():
                # the drain thread may have finished this very box
                # between the wait timing out and the checks above
                if done.is_set():
                    break
                raise RuntimeError("batcher closed with request "
                                   "pending")
        if "error" in box:
            raise RuntimeError(box["error"])
        return box["result"]

    def close(self):
        self._stop.set()
        self.q.put(None)
        self._thread.join(timeout=5)
        # fail anything still queued (the drain thread can exit on the
        # sentinel while real requests remain behind it)
        while True:
            try:
                box = self.q.get_nowait()
            except queue.Empty:
                break
            if box is not None:
                box["error"] = "batcher closed"
                box["done"].set()

    def _run(self):
        import numpy as np

        while not self._stop.is_set():
            first = self.q.get()
            if first is None:
                continue
            batch = [first]
            # sampling params are per-BATCH shape keys: only coalesce
            # requests that share them (others wait for the next cycle)
            deadline = time.monotonic() + self.window_ms / 1e3
            while len(batch) < self.max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    nxt = self.q.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is None:
                    break
                if (nxt["temperature"] == first["temperature"]
                        and nxt["top_k"] == first["top_k"]):
                    batch.append(nxt)
                else:
                    self.q.put(nxt)
                    break

            # EVERYTHING per-batch lives under try: an assembly error
            # (e.g. an int that overflows int32) must fail the batch's
            # waiters, never kill this thread — a dead drain thread
            # would hang every future request forever
            try:
                lens = [len(b["prompt"]) for b in batch]
                if (self.exact_solo and len(batch) == 1
                        and first["temperature"] <= 0
                        and (lens[0] in self._exact_lens
                             or len(self._exact_lens) < 16)):
                    self._exact_lens.add(lens[0])
                    T = lens[0]
                else:
                    T = _bucket(max(lens))
                # batch size is a compile shape too: bucket the batch
                # in UNITS of rows_multiple (power-of-two unit counts)
                # so varying coalesce counts reuse log2(max_batch)
                # programs AND B stays divisible by the mesh's data
                # axes even when dp*fsdp is not a power of two
                units = -(-len(batch) // self.rows_multiple)
                B = _bucket(units, lo=1) * self.rows_multiple
                ids = np.full((B, T), self.pad_id, np.int32)
                for i, b in enumerate(batch):
                    ids[i, T - lens[i]:] = b["prompt"]   # left-pad
                for i in range(len(batch), B):           # dummy rows
                    ids[i] = ids[0]
                pads = np.asarray(
                    [T - ln for ln in lens] +
                    [T - lens[0]] * (B - len(batch)), np.int32)
                out = np.asarray(self.step_fn(
                    ids, pads, first["temperature"], first["top_k"]))
                self.batches_run += 1
                for i, b in enumerate(batch):
                    row = out[i, T - lens[i]:].tolist()
                    b["result"] = row
                    b["done"].set()
            except Exception as e:  # propagate to every waiter
                for b in batch:
                    b["error"] = repr(e)
                    b["done"].set()


def make_app(cfg, params, *, max_new_tokens: int = 64, mesh=None,
             window_ms: float = 5.0, max_batch: int = 8,
             speculative: bool = False, tokenizer=None,
             fused_int4: bool = True):
    """werkzeug WSGI app + its Batcher. ``mesh`` switches the backend
    to the sharded ``make_generate_step`` program; ``speculative``
    routes solo greedy requests through the single-program
    prompt-lookup decoder (repetitive text decodes in fewer model
    passes; see ``generate_speculative_fused``).

    int4 weights take the fused program by DEFAULT: the fused decode
    loop now unpacks nibbles once per generation instead of once per
    step (``quantize.unpack_int4_params``, hoisted ahead of the scan),
    which removed the 612.77-vs-137.07 ms/tok regression that made PR 4
    route int4 to the per-token loop (``BENCH_SWEEP_r05.json``
    ``decode_7b``).
    ``fused_int4=False`` (``--loop-int4``) keeps the per-token loop as
    the measured A/B baseline arm."""
    import jax
    import numpy as np
    from werkzeug.exceptions import BadRequest, HTTPException
    from werkzeug.routing import Map, Rule
    from werkzeug.wrappers import Request, Response

    from kubeflow_rm_tpu.models import (
        generate, generate_fused, generate_speculative_fused,
        make_generate_step,
    )

    int4_params = any(
        isinstance(leaf, dict) and "q4" in leaf
        for leaf in jax.tree_util.tree_leaves(
            params,
            is_leaf=lambda x: isinstance(x, dict) and "q4" in x))
    loop_decode = int4_params and not fused_int4 and mesh is None

    steps = {}  # (total_len, temperature, top_k) -> sharded step
    LOOKUP_N = 3      # kept in ONE place: guard below + the call
    app_stats = {"speculative_requests": 0}

    def step_fn(ids, pad_counts, temperature, top_k):
        B, T = ids.shape
        S = T + max_new_tokens
        key = jax.random.key(0) if temperature <= 0 else \
            jax.random.key(np.random.randint(0, 2**31 - 1))
        if mesh is None:
            # pad==0 means the batcher granted exact-solo (its length
            # set bounds compiles); anything bucketed/padded verifies
            # on the fused path
            if (speculative and B == 1 and temperature <= 0
                    and int(pad_counts[0]) == 0 and T > LOOKUP_N):
                app_stats["speculative_requests"] += 1
                return generate_speculative_fused(
                    params, cfg, ids, max_new_tokens=max_new_tokens,
                    lookup_n=LOOKUP_N)
            if loop_decode:
                return generate(
                    params, cfg, ids, max_new_tokens=max_new_tokens,
                    key=key, temperature=temperature, top_k=top_k,
                    max_len=S, pad_counts=pad_counts)
            return generate_fused(
                params, cfg, ids, max_new_tokens=max_new_tokens,
                key=key, temperature=temperature, top_k=top_k,
                max_len=S, pad_counts=pad_counts)
        if (S, temperature, top_k) not in steps:
            if len(steps) >= 16:   # bound compile accumulation
                steps.pop(next(iter(steps)))
            steps[(S, temperature, top_k)] = make_generate_step(
                params, cfg, mesh, max_new_tokens=max_new_tokens,
                total_len=S, temperature=temperature, top_k=top_k)
        return steps[(S, temperature, top_k)](params, ids, key,
                                              pad_counts)

    rows = 1
    if mesh is not None:
        rows = int(mesh.shape["dp"] * mesh.shape["fsdp"])
    batcher = Batcher(step_fn, max_new_tokens=max_new_tokens,
                      window_ms=window_ms, max_batch=max_batch,
                      rows_multiple=rows,
                      exact_solo=speculative and mesh is None)

    urls = Map([Rule("/generate", endpoint="generate",
                     methods=["POST"]),
                Rule("/healthz", endpoint="healthz")])

    def app(environ, start_response):
        req = Request(environ)
        try:
            endpoint, _ = urls.bind_to_environ(environ).match()
            if endpoint == "healthz":
                resp = Response(json.dumps({"ok": True}),
                                content_type="application/json")
                return resp(environ, start_response)
            body = req.get_json(force=True)
            if not isinstance(body, dict):
                raise BadRequest("body must be a JSON object")
            if tokenizer is not None and "text" in body:
                if not isinstance(body["text"], str):
                    raise BadRequest("text must be a string")
                prompt = list(tokenizer.encode(body["text"]))
            else:
                prompt = body.get("prompt")
            if (not isinstance(prompt, list) or not prompt
                    or not all(isinstance(t, int)
                               and 0 <= t < cfg.vocab_size
                               for t in prompt)):
                raise BadRequest("prompt must be a non-empty list of "
                                 f"token ids in [0, {cfg.vocab_size}) "
                                 "(or pass text with a server-side "
                                 "tokenizer)")
            if len(prompt) > cfg.max_seq_len - max_new_tokens:
                raise BadRequest(f"prompt too long ({len(prompt)}); "
                                 f"limit {cfg.max_seq_len - max_new_tokens}")
            temp = body.get("temperature", 0.0)
            if not isinstance(temp, (int, float)) or not 0 <= temp <= 10:
                raise BadRequest("temperature must be a number in "
                                 "[0, 10]")
            # sampling params are compile keys (static in the fused
            # program): snap temperature to a 0.05 grid so hostile or
            # chatty clients can't force one XLA compile per request
            temp = round(float(temp) * 20) / 20
            top_k = body.get("top_k")
            if top_k is not None and (
                    not isinstance(top_k, int)
                    or not 1 <= top_k <= cfg.vocab_size):
                raise BadRequest("top_k must be an int in "
                                 f"[1, {cfg.vocab_size}]")
            # top_k is a compile key too (static in the fused program
            # and part of the sharded steps cache key): snap it to a
            # small allowed set so a client cycling values can't
            # accumulate one compiled program per distinct k
            if top_k is not None:
                choices = [c for c in TOP_K_CHOICES
                           if c <= cfg.vocab_size] or [1]
                top_k = min(choices, key=lambda c: abs(c - top_k))
            tokens = batcher.submit(prompt, temp, top_k)
            out = {"tokens": tokens}
            if tokenizer is not None:
                try:  # HF tokenizers: strip <s>/</s> markers
                    out["text"] = tokenizer.decode(
                        tokens, skip_special_tokens=True)
                except TypeError:  # minimal tokenizers (tests)
                    out["text"] = tokenizer.decode(tokens)
            resp = Response(json.dumps(out),
                            content_type="application/json")
        except HTTPException as e:
            resp = e
        return resp(environ, start_response)

    app.batcher = batcher
    app.stats = app_stats
    return app


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny")
    ap.add_argument("--hf-model", default=None)
    quant = ap.add_mutually_exclusive_group()
    quant.add_argument("--int8", action="store_true",
                       help="weight-only int8 quantize before serving")
    quant.add_argument("--int4", action="store_true",
                       help="weight-only packed-int4 quantize "
                            "(smallest HBM footprint; per-group "
                            "scales)")
    ap.add_argument("--max-new-tokens", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--hf-tokenizer", default=None,
                    help="HF tokenizer id/path: lets clients pass "
                         '{"text": ...} and get text back')
    ap.add_argument("--speculative", action="store_true",
                    help="route solo greedy requests through the "
                         "prompt-lookup speculative decoder "
                         "(repetitive text decodes in fewer model "
                         "passes; one compile per distinct prompt "
                         "length)")
    ap.add_argument("--loop-int4", action="store_true",
                    help="serve int4 weights via the per-token "
                         "generate loop instead of the fused program "
                         "(A/B baseline arm; fused is the default now "
                         "that the nibble unpack is hoisted out of "
                         "the decode scan)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=0,
                    help="0 = all local devices (with --tp 1 ⇒ "
                         "single-device fused path when 1 device)")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--selftest", action="store_true",
                    help="serve in-process, run one batched round "
                         "trip, exit")
    args = ap.parse_args(argv)

    import jax

    from kubeflow_rm_tpu.models import (
        LlamaConfig, from_hf_llama, init_params, quantize_params,
    )
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh

    cfg = getattr(LlamaConfig, args.preset)()
    if args.hf_model:
        cfg, params = from_hf_llama(args.hf_model, cfg)
    else:
        params = init_params(cfg, jax.random.key(0))
    if args.int8 or args.int4:
        params = quantize_params(params, bits=4 if args.int4 else 8)

    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1 or args.tp > 1:
        fsdp = args.fsdp or max(1, n_dev // args.tp)
        mesh = make_mesh(MeshConfig(fsdp=fsdp, tp=args.tp))
        if args.speculative:
            print("warning: --speculative is single-device only "
                  "(batch-1 lookup decoding); sharded requests take "
                  "the fused path", flush=True)

    tokenizer = None
    if args.hf_tokenizer:
        from transformers import AutoTokenizer
        tokenizer = AutoTokenizer.from_pretrained(args.hf_tokenizer)
        if len(tokenizer) > cfg.vocab_size:
            print(f"warning: tokenizer vocab ({len(tokenizer)}) exceeds "
                  f"model vocab_size ({cfg.vocab_size}) — text requests "
                  "producing out-of-range ids will be rejected",
                  flush=True)

    app = make_app(cfg, params, max_new_tokens=args.max_new_tokens,
                   mesh=mesh, max_batch=args.max_batch,
                   speculative=args.speculative, tokenizer=tokenizer,
                   fused_int4=not args.loop_int4)

    if args.selftest:
        from werkzeug.test import Client
        c = Client(app)
        r = c.post("/generate", json={"prompt": [1, 2, 3]})
        assert r.status_code == 200, r.get_data()
        toks = r.get_json()["tokens"]
        assert len(toks) == 3 + args.max_new_tokens
        print(f"selftest ok: {len(toks)} tokens, "
              f"{app.batcher.batches_run} batch(es)")
        app.batcher.close()
        return 0

    from werkzeug.serving import make_server
    httpd = make_server("0.0.0.0", args.port, app, threaded=True)
    print(f"serving {args.preset} on :{args.port} "
          f"(mesh={'1 device' if mesh is None else dict(zip(mesh.axis_names, mesh.devices.shape))})",
          flush=True)
    httpd.serve_forever()
    return 0


if __name__ == "__main__":
    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
