"""Per-primitive FLOPs/bytes cost model and live-range peak-HBM
estimator over jaxprs.

``estimate(fn, *args, donate_argnums=...)`` traces ``fn`` abstractly
(``jax.make_jaxpr`` — shapes only, nothing materializes, so a 7B train
step costs milliseconds on a laptop) and walks the jaxpr:

- **FLOPs**: ``dot_general`` from its dimension numbers
  (2 * batch * M * N * K), elementwise/reduction primitives at one
  flop per element (transcendentals included — on TPU they are
  bandwidth-bound, not flop-bound), ``scan`` bodies multiplied by trip
  count, ``remat`` recompute counted as executed (so the model charges
  what the chip actually runs, not the 6N convention —
  ``utils.flops`` stays the MFU-accounting source of truth).
- **HBM traffic**: sum of operand+result bytes per primitive — an
  upper bound that ignores XLA fusion, useful for *relative*
  comparisons (e.g. the adam update's ~6 bytes/param/step).
- **Peak HBM**: a linear-scan liveness walk. A value is live from
  definition to last use; jaxpr invars stay resident the whole call
  *unless donated* (the caller keeps non-donated buffers), and a
  donated input's buffer is reused for outputs (XLA input/output
  aliasing), so donation shows up as a genuinely lower peak. This is
  what lets the model PROVE a non-donated train step double-buffers
  its params/optimizer state: ``peak_bytes_no_donation - peak_bytes``
  comes out to about one full TrainState.

Donation is read from two places: the ``donate_argnums`` /
``donate_argnames`` the caller passes here, and the
``donated_invars`` recorded on every ``jit`` equation (so estimating
an already-jitted function honors the donation baked into it).

Known approximations, all conservative (over-estimating peaks):
fusion is ignored (short-lived elementwise temps count while in
scope), ``while`` bodies are costed for one trip (flagged in
``while_loops`` — FLOPs are a lower bound there), and unknown
primitives (custom/pallas calls without an inlineable jaxpr) count
bytes but zero flops, tallied in ``unknown_primitives`` rather than
silently dropped.
"""

from __future__ import annotations

import inspect
import math
from dataclasses import dataclass, field

import numpy as np

import jax
from jax.core import DropVar
from jax.extend import core as jax_core


# primitives that are pure data movement / bookkeeping: bytes, no flops
_MOVEMENT = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "pad", "gather", "scatter", "scatter-add", "iota", "copy",
    "convert_element_type", "bitcast_convert_type", "device_put",
    "stop_gradient", "split", "expand_dims", "real", "imag", "reshard",
    "name",  # ad_checkpoint.checkpoint_name's identity marker
    "sharding_constraint", "optimization_barrier", "select_and_scatter_add",
})

# one flop per output element (comparisons, selects, arithmetic,
# transcendentals — the table is deliberately flat; see module doc)
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "pow", "atan2",
    "and", "or", "xor", "not", "neg", "sign", "abs", "floor", "ceil",
    "round", "is_finite", "exp", "exp2", "expm1", "log", "log1p",
    "sqrt", "rsqrt", "cbrt", "logistic", "tanh", "sin", "cos", "tan",
    "asin", "acos", "atan", "sinh", "cosh", "erf", "erfc", "erf_inv",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp",
    "nextafter", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "population_count", "clz", "square",
})

# ~2 flops/element (a multiply chain or fused two-op lowering)
_TWO_FLOP = frozenset({"integer_pow", "cumsum", "cumprod", "cummax",
                       "cummin", "cumlogsumexp"})

# ops whose output can reuse a dying operand's buffer (XLA buffer
# assignment does this for elementwise lowerings; modeling it keeps a
# chained optimizer update at ~one live tree instead of one per op)
_REUSE_OK = (_ELEMENTWISE | _TWO_FLOP
             | {"convert_element_type", "copy", "reduce_precision",
                "name", "add_any"})

# one flop per INPUT element
_REDUCTION = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_precision", "sort", "top_k",
})


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    dtype = getattr(aval, "dtype", None)
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG keys etc.): jax exposes itemsize on
        # most; default to 4 rather than crash an audit
        itemsize = getattr(dtype, "itemsize", 4)
    return n * itemsize


def _dot_flops(eqn) -> float:
    (lhs_c, rhs_c), (lhs_b, _rhs_b) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    batch = math.prod(int(lhs[d]) for d in lhs_b)
    contract = math.prod(int(lhs[d]) for d in lhs_c)
    lhs_free = math.prod(int(s) for d, s in enumerate(lhs)
                         if d not in lhs_c and d not in lhs_b)
    rhs_free = math.prod(int(s) for d, s in enumerate(rhs)
                         if d not in rhs_c and d not in _rhs_b)
    return 2.0 * batch * lhs_free * rhs_free * contract


def _conv_flops(eqn) -> float:
    # 2 * output elements * kernel spatial * in-features / groups
    out = math.prod(int(d) for d in eqn.outvars[0].aval.shape)
    k = eqn.invars[1].aval.shape
    spatial = math.prod(int(d) for d in k[2:])
    groups = int(eqn.params.get("feature_group_count", 1))
    return 2.0 * out * spatial * int(k[1]) * groups


@dataclass
class _Walk:
    """Accumulators threaded through one (sub)jaxpr walk."""
    flops: float = 0.0
    traffic: float = 0.0
    peak: int = 0
    unknown: dict = field(default_factory=dict)
    while_loops: int = 0


@dataclass(frozen=True)
class CostEstimate:
    """What one call of the estimated function costs the chip."""
    flops: float                    # executed flops (incl. remat recompute)
    hbm_traffic_bytes: float        # un-fused operand+result traffic
    peak_bytes: int                 # live-range peak, donation honored
    peak_bytes_no_donation: int     # same walk, donation ignored
    arg_bytes: int                  # resident input footprint
    out_bytes: int                  # result footprint
    unknown_primitives: dict        # name -> count (bytes counted, 0 flops)
    while_loops: int                # bodies costed at 1 trip (flops floor)

    @property
    def donation_savings_bytes(self) -> int:
        return self.peak_bytes_no_donation - self.peak_bytes

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_traffic_bytes": self.hbm_traffic_bytes,
            "peak_bytes": self.peak_bytes,
            "peak_bytes_no_donation": self.peak_bytes_no_donation,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "unknown_primitives": dict(self.unknown_primitives),
            "while_loops": self.while_loops,
        }


def _child_jaxprs(eqn):
    """(closed_jaxpr, flop_multiplier, donated_invars) children of a
    call-like equation; empty for leaf primitives."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "jit":
        return [(p["jaxpr"], 1, p.get("donated_invars"))]
    if name == "scan":
        return [(p["jaxpr"], int(p.get("length", 1)), None)]
    if name == "while":
        return [(p["cond_jaxpr"], 1, None), (p["body_jaxpr"], 1, None)]
    if name == "cond":
        return [(b, 1, None) for b in p["branches"]]
    out = []
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = p.get(key)
        if isinstance(sub, jax_core.ClosedJaxpr):
            out.append((sub, 1, None))
        elif isinstance(sub, jax_core.Jaxpr):
            out.append((jax_core.ClosedJaxpr(sub, ()), 1, None))
    return out


def _leaf_cost(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    out_elems = sum(
        math.prod(int(d) for d in getattr(v.aval, "shape", ()))
        for v in eqn.outvars)
    in_elems = sum(
        math.prod(int(d) for d in getattr(v.aval, "shape", ()))
        for v in eqn.invars if not isinstance(v, jax_core.Literal))
    if name in _ELEMENTWISE:
        return float(out_elems)
    if name in _TWO_FLOP:
        return 2.0 * out_elems
    if name in _REDUCTION:
        return float(in_elems)
    return 0.0


def _walk(closed: jax_core.ClosedJaxpr, donated, honor: bool,
          acc: _Walk) -> tuple[int, int, int]:
    """Liveness walk of one closed jaxpr. Returns (peak, in_bytes,
    out_bytes) for THIS jaxpr; flops/traffic/flags accumulate into
    ``acc`` (scan multipliers applied by the caller via repeated
    flop accounting below)."""
    jaxpr = closed.jaxpr
    donated = tuple(donated) if donated else (False,) * len(jaxpr.invars)

    last_use: dict = {}
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jax_core.Var):
                last_use[v] = idx
    for v in jaxpr.outvars:
        if isinstance(v, jax_core.Var):
            last_use[v] = len(jaxpr.eqns)  # live through the end

    live: dict = {}
    freeable: set = set()
    in_bytes = 0
    for v, const in zip(jaxpr.constvars, closed.consts):
        live[v] = _aval_bytes(v.aval)
    for i, v in enumerate(jaxpr.invars):
        live[v] = _aval_bytes(v.aval)
        in_bytes += live[v]
        if honor and i < len(donated) and donated[i]:
            freeable.add(v)

    peak = sum(live.values())
    for idx, eqn in enumerate(jaxpr.eqns):
        scratch = 0
        donated_in = 0
        children = _child_jaxprs(eqn)
        if eqn.primitive.name == "while":
            acc.while_loops += 1
        if children:
            for sub, mult, sub_donated in children:
                sub_acc = _Walk(unknown=acc.unknown)
                c_peak, c_in, c_out = _walk(sub, sub_donated, honor,
                                            sub_acc)
                acc.flops += sub_acc.flops * mult
                acc.traffic += sub_acc.traffic * mult
                acc.while_loops += sub_acc.while_loops
                scratch = max(scratch, c_peak - c_in - c_out)
            sub_donated = children[0][2]
            if honor and sub_donated:
                # a donated buffer is consumed by the call and its
                # storage reused for outputs (XLA i/o aliasing) —
                # but only when this call is the buffer's final use;
                # a later read forces XLA to copy instead of alias
                for i, v in enumerate(eqn.invars):
                    if (i < len(sub_donated) and sub_donated[i]
                            and isinstance(v, jax_core.Var)
                            and last_use.get(v) == idx and v in live):
                        donated_in += live[v]
                        freeable.add(v)
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars
                        if not isinstance(v, DropVar))
        eqn_in_bytes = sum(
            _aval_bytes(v.aval) for v in set(
                v for v in eqn.invars if isinstance(v, jax_core.Var)))
        reused = 0
        if not children:
            acc.flops += _leaf_cost(eqn)
            if eqn.primitive.name in _REUSE_OK:
                # operand reuse is fusion modeling, not donation: it
                # applies in the no-donation walk too (temps are
                # freeable in both; donated invars only when honored)
                dying = sum(
                    live[v] for v in set(
                        v for v in eqn.invars
                        if isinstance(v, jax_core.Var))
                    if v in freeable and last_use.get(v) == idx
                    and v in live)
                reused = min(out_bytes, dying)
        acc.traffic += eqn_in_bytes + out_bytes

        out_extra = max(0, out_bytes - donated_in - reused)
        peak = max(peak, sum(live.values()) + out_extra + max(0, scratch))

        for v in eqn.outvars:
            if isinstance(v, DropVar):
                continue
            live[v] = _aval_bytes(v.aval)
            freeable.add(v)  # temps are always reclaimable
        for v in set(v for v in eqn.invars if isinstance(v, jax_core.Var)):
            if last_use.get(v) == idx and v in freeable:
                live.pop(v, None)
    peak = max(peak, sum(live.values()))
    out_bytes_total = sum(
        _aval_bytes(v.aval) for v in jaxpr.outvars
        if isinstance(v, jax_core.Var))
    return peak, in_bytes, out_bytes_total


def estimate_jaxpr(closed: jax_core.ClosedJaxpr,
                   donated_invars=None) -> CostEstimate:
    """Cost a ClosedJaxpr directly. ``donated_invars`` is a bool per
    (flattened) invar; ``jit`` sub-calls additionally contribute the
    donation baked into them."""
    acc = _Walk()
    peak, in_b, out_b = _walk(closed, donated_invars, True, acc)
    acc2 = _Walk()
    peak_nd, _, _ = _walk(closed, None, False, acc2)
    return CostEstimate(
        flops=acc.flops, hbm_traffic_bytes=acc.traffic,
        peak_bytes=peak, peak_bytes_no_donation=peak_nd,
        arg_bytes=in_b, out_bytes=out_b,
        unknown_primitives=_unknown_prims(closed),
        while_loops=acc.while_loops)


_KNOWN = (_MOVEMENT | _ELEMENTWISE | _TWO_FLOP | _REDUCTION
          | {"dot_general", "conv_general_dilated", "jit", "scan",
             "while", "cond", "remat2", "checkpoint", "custom_jvp_call",
             "custom_vjp_call", "custom_vjp_call_jaxpr", "closed_call",
             "core_call", "xla_call", "random_seed", "random_wrap",
             "random_bits", "random_unwrap", "random_fold_in",
             "threefry2x32", "add_any", "select_and_gather_add",
             "erf_inv", "stop_gradient"})


def _unknown_prims(closed: jax_core.ClosedJaxpr, out=None) -> dict:
    out = {} if out is None else out
    for eqn in closed.jaxpr.eqns:
        children = _child_jaxprs(eqn)
        for sub, _, _ in children:
            _unknown_prims(sub, out)
        if not children and eqn.primitive.name not in _KNOWN:
            out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
    return out


def _donated_mask(fn, args, donate_argnums, donate_argnames):
    """Flatten per-argument donation down to per-leaf invar flags, the
    layout ``jax.make_jaxpr`` presents."""
    donate = set(donate_argnums or ())
    if donate_argnames:
        try:
            params = list(inspect.signature(fn).parameters)
            donate |= {params.index(n) for n in donate_argnames}
        except (ValueError, TypeError) as exc:
            raise ValueError(
                f"cannot resolve donate_argnames={donate_argnames!r} "
                f"against {fn!r}") from exc
    mask = []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        mask.extend([i in donate] * n)
    return tuple(mask)


def estimate(fn, *args, donate_argnums=(), donate_argnames=(),
             **kwargs) -> CostEstimate:
    """Trace ``fn(*args, **kwargs)`` abstractly and cost it. ``args``
    may be real arrays or ``jax.ShapeDtypeStruct`` trees — nothing is
    executed or materialized."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    mask = _donated_mask(fn, args, donate_argnums, donate_argnames)
    return estimate_jaxpr(closed, mask)


# ---------------------------------------------------------------------------
# self-check: the CI gate's smoke that the model's arithmetic is sane
# ---------------------------------------------------------------------------

def selfcheck() -> list[str]:
    """Verify the cost model against hand-computable programs. Returns
    a list of failure strings (empty = pass) so the CLI can gate on
    it without pytest."""
    import jax.numpy as jnp

    failures: list[str] = []

    def expect(label, got, want, tol=0.0):
        lo, hi = want * (1 - tol), want * (1 + tol)
        if not (lo <= got <= hi):
            failures.append(f"{label}: got {got}, want {want}"
                            + (f" ±{tol:.0%}" if tol else ""))

    # (64, 128) @ (128, 32): 2*M*N*K flops exactly
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    est = estimate(jnp.matmul, a, b)
    expect("matmul flops", est.flops, 2 * 64 * 32 * 128)

    # donation: f(x) = x + 1 jitted with donate_argnums=(0,) must peak
    # at ~one buffer; non-donated at ~two (the double-buffer proof in
    # miniature)
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32)
    nbytes = (1 << 20) * 4
    don = estimate(jax.jit(lambda v: v + 1.0, donate_argnums=(0,)), x)
    if not (nbytes <= don.peak_bytes < 2 * nbytes):
        failures.append(f"donated peak {don.peak_bytes} not in "
                        f"[{nbytes}, {2 * nbytes})")
    if don.peak_bytes_no_donation < 2 * nbytes:
        failures.append(f"non-donated peak {don.peak_bytes_no_donation}"
                        f" < {2 * nbytes}: double-buffer not modeled")

    # scan multiplies body flops by trip count
    def scanned(v):
        return jax.lax.scan(lambda c, _: (c * 2.0, None), v,
                            None, length=10)[0]
    est = estimate(scanned, x)
    expect("scan flops", est.flops, 10 * (1 << 20))

    return failures
