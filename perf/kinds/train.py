"""Kind ``train``: ``training.loop.fit()`` on a one-device mesh over
packed documents, a fresh batch every step from the seeded generator
running in the loop.

Grown from ``chip_smoke.py``'s ``train_phase`` (copied, not imported).
One ``fit()`` call does everything: the generator that feeds it is the
run's clock. Its first batches are the steps the reference follows
and the warm-up; then it marks the window's start and feeds batches
for ``--seconds`` while keeping ``in_flight`` steps ahead of the
device: it waits for step ``i - in_flight`` before it hands out batch
``i``, so that a host that stands still for some seconds finds the
chip fed when it comes back. When the time is up it hands out nothing
more, waits for every step it sent and reads the clock after that
wait: ``train_tok_s`` is all of that work over all of that time, so
nothing unfinished is counted and a stall that runs to the window's
end still counts as time. fit() ends on the exhausted stream. The
step that fit() builds is wrapped, from here, by a span that keeps
each step's metrics (on the device, unread) and, after the first and
the third step, per-leaf norms of Adam's first moment and of the
parameters' change: what the comparison with the reference needs of
the very object the window
then drives.
"""

import dataclasses
import functools
import gc
import math
import sys
import time
from unittest import mock

import numpy as np

from perf import flops, harness, reference, traffic_gen
from perf.kinds.serve import llama_config


#: a wait for a step's end that returns this soon found it ended
LATE_S = 1e-3


def _leaf_names(tree) -> list[str]:
    import jax
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(k.key) for k in p) for p, _ in paths]


class StepSpan:
    """Wraps the step fit() builds: same object, same call, with the
    harness's eyes on what goes in and comes out."""

    def __init__(self, tc, key, check_steps):
        import jax
        import jax.numpy as jnp

        self.metrics = []           # one dict of device scalars a step
        self.mu_norms = None
        self.change_norms = None
        self.key, self.check_steps = key, check_steps

        def norms(tree):
            return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                    for x in jax.tree_util.tree_leaves(tree)]

        self._norms = jax.jit(norms)
        self._tc = tc

    def _change(self, key, params):
        """Per-leaf norm of the parameters' change since the start. The
        start is drawn again from the seed by the program's own rule,
        one leaf to a call, so that no second copy of the weights is
        ever held."""
        import jax
        import jax.numpy as jnp

        from kubeflow_rm_tpu.models.llama import init_leaf
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        keys = jax.random.split(key, len(flat))
        cfg = self._tc.model

        @functools.partial(jax.jit, static_argnums=(2,))
        def one(k, leaf, name):
            start = init_leaf(cfg, name, leaf.shape, k)
            return jnp.sqrt(jnp.sum(jnp.square(
                leaf.astype(jnp.float32) - start.astype(jnp.float32))))

        return [one(k, leaf, path[-1].key)
                for (path, leaf), k in zip(flat, keys)]

    def wrap(self, step_fn):
        def step(state, batch):
            state, metrics = step_fn(state, batch)
            self.metrics.append(metrics)
            n = len(self.metrics)
            if n == 1:
                self.mu_norms = self._norms(_first_moment(state.opt_state))
            if n == self.check_steps:
                self.change_norms = self._change(self.key, state.params)
            return state, metrics
        return step


def _first_moment(opt_state):
    """Adam's ``mu`` out of the optimizer's chained state."""
    import jax
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def attended_pairs(segments) -> float:
    """Mean over the rows of the (query, key) pairs a row's attention
    has to visit: a token attends itself and those before it in its
    own document, n (n + 1) / 2 for a stretch of n tokens."""
    total = 0.0
    for row in segments:
        edges = np.flatnonzero(np.diff(row)) + 1
        runs = np.diff(np.concatenate([[0], edges, [len(row)]]))
        live = row[np.concatenate([[0], edges])] != 0
        total += float((runs[live] * (runs[live] + 1) / 2).sum())
    return total / len(segments)


class Feed:
    """The generator fit() draws from, and the run's clock."""

    def __init__(self, batches, span, mix, seconds, tracer, annotate):
        self.batches, self.span, self.mix = batches, span, mix
        self.seconds, self.tracer, self.annotate = seconds, tracer, annotate
        self.kept = []              # the batches the reference follows
        self.done_at = {}           # step (1-based) -> host time it ended
        self.tokens = {}            # step -> its non-padding tokens
        self.pairs = {}             # step -> (query, key) pairs a row
        self.t0 = None
        self.t_closed = None        # the time was found up: no more sent
        self.t_end = None           # read once every step sent has ended
        self.first_window_step = None
        self.last_window_step = None
        self.compiles_at_t0 = None
        # the slice opens once the first of these steps has ended and
        # closes once the second has
        self.traced = None
        # waits of the window that found their step already ended, the
        # longest run of them: the host came late that many steps running
        self.behind = self.behind_max = 0

    def _wait(self, step):
        """Block until ``step`` has ended; note when."""
        import jax
        if step >= 1 and step not in self.done_at:
            t = time.perf_counter()
            jax.block_until_ready(self.span.metrics[step - 1]["loss"])
            self.done_at[step] = time.perf_counter()
            late = self.done_at[step] - t < LATE_S
            self.behind = self.behind + 1 if late else 0
            self.behind_max = max(self.behind_max, self.behind)

    def __call__(self, clock):
        mix = self.mix
        warm, ahead = int(mix["warmup_steps"]), int(mix["in_flight"])
        opens = closes = None       # the slice, by the last step ended
        i = 0                       # the step this batch is for, from 1
        while True:
            i += 1
            if i <= warm + 1:
                # before the window nothing runs ahead: each step ends
                # before the next batch is made
                self._wait(i - 1)
            else:
                self._wait(i - ahead)
            now = time.perf_counter()
            if i == warm + 1:
                self.t0 = self.done_at[warm]
                self.first_window_step = i
                self.compiles_at_t0 = clock.programs
                self.behind = self.behind_max = 0
                if self.tracer:
                    # by steps ended, past the filling of the queue:
                    # the slice is of the steady state whatever
                    # in_flight is
                    opens = warm + max(1, int(mix["trace"]["after_steps"]))
                    closes = opens + int(mix["trace"]["steps"])
                    self.traced = (opens, closes)
            if self.t0 is not None and now - self.t0 >= self.seconds:
                self.t_closed = now
                break
            if self.tracer and i - ahead == opens:
                self.tracer.start()
            if self.tracer and i - ahead == closes:
                # steps up to i - ahead have ended: the slice holds
                # whole steps and the start of one more
                self.tracer.stop()
            with self.annotate("perf.make_batch"):
                batch = next(self.batches)
            if i <= int(mix["check_steps"]):
                self.kept.append(batch)
            self.tokens[i] = int((batch["segments"] != 0).sum())
            self.pairs[i] = attended_pairs(batch["segments"])
            yield batch
        if self.tracer:
            self.tracer.stop()
        # the window closes: nothing more is sent, every step sent is
        # waited for, in its order, and the clock is read after the last
        self.last_window_step = i - 1
        for step in range(max(1, i - ahead), i):
            self._wait(step)
        self.t_end = self.done_at[self.last_window_step]


def run(*, cell, args, devices, clock, t_start, dry) -> dict:
    import jax

    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_rm_tpu.training import loop as loop_mod
    from kubeflow_rm_tpu.training.loop import LoopConfig, fit
    from kubeflow_rm_tpu.training.optim import OptimConfig
    from kubeflow_rm_tpu.training.train import TrainConfig, init_train_state

    config, mix = cell["config"], cell["traffic"]
    tr = config["training"]
    d = reference.dims_of(config)
    cfg = dataclasses.replace(llama_config(config),
                              remat_policy=tr["remat"])
    tc = TrainConfig(model=cfg, optim=OptimConfig(**tr["optim"]),
                     z_loss=tr["z_loss"])
    rows = tr["microbatch"] * tr["grad_accum"]
    mesh = make_mesh(MeshConfig(fsdp=1), devices=list(devices))
    key = jax.random.key(args.seed)
    annotate = jax.profiler.TraceAnnotation

    # ---- one fit(): set-up, the window, the end -----------------------
    state = jax.jit(lambda k: init_train_state(tc, k))(key)
    names = _leaf_names(state.params)
    span = StepSpan(tc, key, int(mix["check_steps"]))
    tracer = harness.TraceSlice() if args.trace else None
    feed = Feed(traffic_gen.train_batches(mix, args.seed, cfg.vocab_size,
                                          tr["seq_len"], rows),
                span, mix, args.seconds, tracer, annotate)
    real_make = loop_mod.make_train_step

    def make_spanned(*a, **kw):
        return span.wrap(real_make(*a, **kw))

    with mock.patch.object(loop_mod, "make_train_step", make_spanned):
        state, _history = fit(
            tc, mesh, feed(clock),
            LoopConfig(total_steps=10 ** 9, log_every=10 ** 9,
                       seed=args.seed, grad_accum=tr["grad_accum"]),
            state=state)
    compiles = clock.programs - feed.compiles_at_t0
    memory_peak = harness.memory_peak(devices)

    # ---- what the window did -------------------------------------------
    # every step handed out before the time was up, over window start ->
    # the clock read once the last of them had ended
    steps_run = len(span.metrics)
    counted = list(range(feed.first_window_step, feed.last_window_step + 1))
    attempted = len(counted)
    tokens = sum(feed.tokens[s] for s in counted)
    span_s = feed.t_end - feed.t0
    train_tok_s = tokens / span_s if counted else math.nan
    setup_s = feed.t0 - t_start
    ahead = int(mix["in_flight"])
    # the slice opens once step traced[0] has ended and closes once
    # step traced[1] has: between the step it cuts at its start and
    # the one it cuts at its end lie these
    whole = [feed.pairs[s] for s in range(feed.traced[0] + 2,
                                          feed.traced[1] + 1)
             if s in feed.pairs] if feed.traced else []
    losses = [float(x) for x in jax.device_get(
        [m["loss"] for m in span.metrics])]
    failed = sum(1 for x in losses[feed.first_window_step - 1:]
                 if not math.isfinite(x))

    # ---- the program's numbers, then free it, then the reference -------
    check = int(mix["check_steps"])
    b1 = tr["optim"]["b1"]
    program = {
        "loss": losses[:check],
        "first_grad_norm": {n: float(x) / (1.0 - b1) for n, x in
                            zip(names, jax.device_get(span.mu_norms))},
        "param_change_norm": dict(zip(names, (
            float(x) for x in jax.device_get(span.change_norms)))),
    }
    ends = [feed.t0] + [feed.done_at[s] for s in counted]
    step_ms_max = 1e3 * max(np.diff(ends)) if counted else None
    batches, tokens_a_step = feed.kept, feed.tokens.get(1)
    behind_max, window_s = feed.behind_max, feed.t_end - feed.t0
    drain_s = feed.t_end - feed.t_closed
    del state, span, feed, _history
    gc.collect()
    compared, info = _check(program, batches, config, mix, d, args)

    metrics = {"train_tok_s": train_tok_s, "setup_s": setup_s}
    trace = tracer.reduce(args.dump_trace) if tracer else None
    if args.trace:
        ctx = {
            "cell": cell, "dims": d, "trace": trace,
            "peaks": None if dry else flops.peaks(devices[0].device_kind),
            "train_tok_s": train_tok_s, "seq_len": tr["seq_len"],
            "microbatch_rows": tr["microbatch"],
            "grad_accum": tr["grad_accum"],
            "attended_pairs_whole_steps": whole,
        }
        metrics.update(harness.read_per_layer(cell, ctx))
        info["notes"] = ctx["notes"]
    info.update({"steps_in_window": len(counted), "steps_run": steps_run,
                 "tokens_a_step": tokens_a_step,
                 "step_ms": 1e3 * span_s / len(counted) if counted else None,
                 "step_ms_max": step_ms_max,
                 "window_s": window_s,
                 "drain_s": drain_s,
                 "in_flight": ahead,
                 # in_flight of them in a row: the chip ran dry
                 "host_behind_steps_max": behind_max,
                 "last_loss": losses[-1],
                 "compile_programs_total": clock.programs,
                 "compile_s_total": clock.seconds})
    return {"compared": compared, "compiles_in_window": compiles,
            "attempted": attempted, "failed": failed,
            "metrics": metrics, "memory_peak_bytes": memory_peak,
            "trace": trace, "info": info}


def worst_leaf_gap(program: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The widest gap, over the leaves, between the program's norm and
    the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    med = float(np.median([ref[n] for n in ref if n not in skip]))
    worst, where = 0.0, ""
    for n in ref:
        if n in skip:
            continue
        gap = abs(program[n] - ref[n]) / max(ref[n], med)
        if gap > worst:
            worst, where = gap, n
    return worst, where


def _check(program, batches, config, mix, d, args):
    """Each followed step's loss, the first gradient's norm as the
    optimizer got it and the parameters' change after the last
    followed step, the two norms by the worst leaf. Leaves whose
    gradient is nought to rounding in the reference (under a thousandth
    of the median leaf's) are left out of the change: under Adam they
    move by round-off alone."""
    limits = mix["check"]["limits"]
    t_ref = time.perf_counter()
    ref = _reference(batches, config, d, args.seed)
    compared, info = _compare(program, ref, limits)
    if args.control:
        # the control (the reference at a lower precision) or a planted
        # fault ("half-batch"), put in the program's place: its numbers
        # go through the same verdict, the program's own to info
        what = args.control
        quant, fault = (None, what) if what == "half-batch" else (what, None)
        low = _reference(batches, config, d, args.seed, quant, fault)
        info.update({"control": what, "program": {
            k: v["value"] for k, v in compared.items()}})
        compared, _ = _compare(low, ref, limits, quiet=True)
    info["reference_s"] = time.perf_counter() - t_ref
    return compared, info


def _reference(batches, config, d, seed, quant=None, fault=None):
    import jax.numpy as jnp
    weights = reference.init_weights(
        d, seed, jnp.dtype(config["precision"]["params"]))
    weights = {n: a.astype(jnp.float32) for n, a in weights.items()}
    return reference.train_steps(weights, batches, d, config["training"],
                                 quant=quant, fault=fault)


def _compare(program, ref, limits, quiet=False):
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(program["loss"], ref["loss"]))
    g_gap, g_leaf = worst_leaf_gap(program["first_grad_norm"],
                                   ref["first_grad_norm"])
    med = float(np.median(list(ref["first_grad_norm"].values())))
    still = [n for n, g in ref["first_grad_norm"].items()
             if g < 1e-3 * med]
    p_gap, p_leaf = worst_leaf_gap(program["param_change_norm"],
                                   ref["param_change_norm"], skip=still)
    compared = {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "first_grad_norm_gap": {"value": g_gap,
                                "limit": limits["first_grad_norm_gap"]},
        "param_change_gap": {"value": p_gap,
                             "limit": limits["param_change_gap"]},
    }
    info = {"loss_program": program["loss"], "loss_reference": ref["loss"],
            "first_grad_worst_leaf": g_leaf, "param_change_worst_leaf": p_leaf,
            "leaves_left_out": still}
    if quiet:
        return compared, info
    print(f"leaves program/reference first_grad "
          f"{_pairs(program['first_grad_norm'], ref['first_grad_norm'])}",
          file=sys.stderr)
    print(f"leaves program/reference param_change "
          f"{_pairs(program['param_change_norm'], ref['param_change_norm'])}",
          file=sys.stderr)
    return compared, info


def _pairs(a, b):
    return {n: (round(a[n], 6), round(b[n], 6)) for n in b}
