"""Admission-time workload pricing: the memplan walker as a service.

:mod:`.memplan` walks the real jitted train step of a fixed ladder and
writes an offline artifact. This module is the same machinery shaped
for the control plane's admission path: a **declared workload** (the
JSON a user puts in ``tpu.kubeflow.org/declared-workload``) is parsed,
bounded, traced abstractly (eval_shape — nothing materializes, no
device needed) and priced against the target slice's HBM budget. The
verdict carries the full breakdown (params / grads / optimizer state /
logits / workspace), which phase binds, and the predicted FLOPs per
step the scheduler uses as a packing tiebreak.

Two things make this admissible in a webhook:

- **a memo cache** keyed by the canonical declaration + chip count:
  tracing a 2.7B step costs seconds of CPU, but every replica of a
  storm declares the same few configs, so the steady state is a dict
  lookup under a leaf lock;
- **hard schema bounds** (layer/dim/seq/batch caps) so a hostile
  declaration can't turn the webhook into a tracing DoS.

The **advisor** (:func:`advise`) answers the natural follow-up to a
rejection: walk a short ladder of progressively cheaper knob settings
(remat=full -> halve the microbatch -> offload=optimizer -> both) and
return the first rung that fits — the exact dict the user can paste
back into the declaration, priced by the same walker that rejected the
original.

Sharding model: the declared step is priced on ONE chip and divided by
the slice's chip count (fsdp shards params/grads/opt state and the
batch dimension — the same per-chip ≈ peak/chips assumption
MEMPLAN_r01's v5p-8 north-star row uses). The budget applies the bench
family's usable-HBM fraction (15.75/16) and the 5% allocator margin.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

from kubeflow_rm_tpu.analysis.lockgraph import make_lock
from kubeflow_rm_tpu.analysis.jaxcheck.memplan import (
    CHIP_HBM_GIB,
    GB,
    HBM_MARGIN,
    USABLE_GIB,
)

#: fraction of raw HBM the allocator exposes (bench.py's measured
#: 15.75/16 figure, applied uniformly across generations)
USABLE_FRACTION = USABLE_GIB / CHIP_HBM_GIB

OPTIMS = ("adamw", "adafactor")
REMATS = ("dots", "full", "attn", "mlp", "attn+mlp")
OFFLOADS = (None, "optimizer")

# schema bounds: a declaration is user input reaching an abstract
# tracer — cap everything that scales trace cost
MAX_LAYERS = 200
MAX_DIM = 32768
MAX_SEQ = 65536
MAX_BATCH = 65536
MAX_VOCAB = 1_000_000

_MODEL_DIM_KEYS = ("dim", "n_layers", "n_heads", "n_kv_heads",
                   "hidden_dim", "vocab_size")


#: keys of ``models.nemotron_h.NemotronHConfig`` a Llama has none of
_HYBRID_KEYS = frozenset({"pattern", "mamba_heads", "mamba_head_dim",
                          "state_size", "n_routed_experts",
                          "experts_held", "latent_dim"})


class DeclarationError(ValueError):
    """The declared-workload JSON is malformed or out of bounds."""


@dataclass(frozen=True)
class DeclaredWorkload:
    """A parsed, bounds-checked workload declaration."""
    preset: str | None           # LlamaConfig preset name, or None
    model: tuple | None          # explicit dims (sorted kv pairs)
    optim: str = "adafactor"
    batch: int = 32
    grad_accum: int = 32
    remat: str = "full"
    seq: int | None = None       # None: the preset's max_seq_len
    param_dtype: str = "bfloat16"
    offload: str | None = None
    tenant: str = "default"

    @property
    def microbatch(self) -> int:
        return self.batch // self.grad_accum

    def to_dict(self) -> dict:
        d = {"optim": self.optim, "batch": self.batch,
             "grad_accum": self.grad_accum, "remat": self.remat,
             "param_dtype": self.param_dtype}
        if self.preset:
            d["preset"] = self.preset
        if self.model:
            d["model"] = dict(self.model)
        if self.seq:
            d["seq"] = self.seq
        if self.offload:
            d["offload"] = self.offload
        if self.tenant != "default":
            d["tenant"] = self.tenant
        return d

    def key(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True,
                          separators=(",", ":"))


def parse(raw: str | dict) -> DeclaredWorkload:
    """Parse + validate a declaration. Raises :class:`DeclarationError`
    on anything malformed — callers degrade to chip-count-only
    admission, they do not reject."""
    if isinstance(raw, str):
        try:
            raw = json.loads(raw)
        except (TypeError, ValueError) as e:
            raise DeclarationError(f"not valid JSON: {e}") from None
    if not isinstance(raw, dict):
        raise DeclarationError("declaration must be a JSON object")

    preset = raw.get("preset")
    model_raw = raw.get("model")
    # the walk traces the dense decoder's training step: a declaration
    # of another family is refused by name, not priced as if its
    # Llama-shaped keys were the whole model
    family = raw.get("family", "llama")
    foreign = (sorted(_HYBRID_KEYS & set(model_raw))
               if isinstance(model_raw, dict) else [])
    if family != "llama" or foreign:
        raise DeclarationError(
            f"model family {family!r}"
            + (f" (keys {foreign})" if foreign else "")
            + " is not priced: the pricer walks the dense decoder's "
              "training step only (models.nemotron_h has no training "
              "path)")
    if preset is not None:
        from kubeflow_rm_tpu.models.llama import LlamaConfig
        if not isinstance(preset, str) or not hasattr(LlamaConfig,
                                                      preset) \
                or preset.startswith("_"):
            raise DeclarationError(f"unknown model preset {preset!r}")
        model = None
    elif model_raw is not None:
        if not isinstance(model_raw, dict):
            raise DeclarationError("model must be an object of dims")
        dims = {}
        for k in _MODEL_DIM_KEYS:
            v = model_raw.get(k)
            if not isinstance(v, int) or v < 1:
                raise DeclarationError(
                    f"model.{k} must be a positive int")
            dims[k] = v
        if dims["n_layers"] > MAX_LAYERS or dims["dim"] > MAX_DIM \
                or dims["vocab_size"] > MAX_VOCAB:
            raise DeclarationError("model dims exceed pricing bounds")
        if dims["dim"] % dims["n_heads"] != 0:
            raise DeclarationError("dim must divide by n_heads")
        model = tuple(sorted(dims.items()))
    else:
        raise DeclarationError(
            "declaration needs 'preset' or explicit 'model' dims")

    optim = raw.get("optim", "adafactor")
    if optim not in OPTIMS:
        raise DeclarationError(f"optim must be one of {OPTIMS}")
    remat = raw.get("remat", "full")
    if remat not in REMATS:
        raise DeclarationError(f"remat must be one of {REMATS}")
    offload = raw.get("offload")
    if offload not in OFFLOADS:
        raise DeclarationError(f"offload must be one of {OFFLOADS}")
    batch = raw.get("batch", 32)
    accum = raw.get("grad_accum", batch)
    for name, v, cap in (("batch", batch, MAX_BATCH),
                         ("grad_accum", accum, MAX_BATCH)):
        if not isinstance(v, int) or not 1 <= v <= cap:
            raise DeclarationError(
                f"{name} must be an int in [1, {cap}]")
    if batch % accum != 0:
        raise DeclarationError("batch must divide by grad_accum")
    seq = raw.get("seq")
    if seq is not None and (not isinstance(seq, int)
                            or not 16 <= seq <= MAX_SEQ):
        raise DeclarationError(f"seq must be an int in [16, {MAX_SEQ}]")
    param_dtype = raw.get("param_dtype", "bfloat16")
    if param_dtype not in ("bfloat16", "float32"):
        raise DeclarationError(
            "param_dtype must be 'bfloat16' or 'float32'")
    tenant = raw.get("tenant", "default")
    if not isinstance(tenant, str) or len(tenant) > 63:
        raise DeclarationError("tenant must be a short string")
    return DeclaredWorkload(preset=preset, model=model, optim=optim,
                            batch=batch, grad_accum=accum, remat=remat,
                            seq=seq, param_dtype=param_dtype,
                            offload=offload, tenant=tenant)


# ---- the walker ------------------------------------------------------

def _tree_bytes(tree) -> int:
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "size"):
            total += leaf.size * getattr(leaf.dtype, "itemsize", 4)
    return total


def _model_config(decl: DeclaredWorkload):
    import jax.numpy as jnp

    from kubeflow_rm_tpu.models.llama import LlamaConfig

    kw: dict = {
        "param_dtype": (jnp.bfloat16 if decl.param_dtype == "bfloat16"
                        else jnp.float32),
        "remat_policy": decl.remat,
    }
    if decl.seq:
        kw["max_seq_len"] = decl.seq
    if decl.preset:
        return getattr(LlamaConfig, decl.preset)(**kw)
    return LlamaConfig(**dict(decl.model), **kw)


def _walk(decl: DeclaredWorkload) -> dict:
    """Trace the declared step and return the raw byte/flop tallies.
    Expensive (seconds) — always reached through the memo cache."""
    import jax
    import jax.numpy as jnp

    from kubeflow_rm_tpu.analysis.jaxcheck.costmodel import estimate
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_rm_tpu.training.optim import OptimConfig
    from kubeflow_rm_tpu.training.train import (
        TrainConfig, init_train_state, make_train_step,
    )

    model = _model_config(decl)
    optim_kw: dict = {"factored": decl.optim == "adafactor"}
    if decl.offload:
        optim_kw["offload"] = decl.offload
    cfg = TrainConfig(model=model, optim=OptimConfig(**optim_kw))
    state = jax.eval_shape(
        lambda k: init_train_state(cfg, k), jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(), jax.devices()[:1])
    batch = {k: jax.ShapeDtypeStruct((decl.batch, model.max_seq_len),
                                     jnp.int32)
             for k in ("tokens", "labels")}
    params_b = _tree_bytes(state.params)
    opt_b = _tree_bytes(state.opt_state)

    if decl.offload == "optimizer":
        # the streamed step: on-chip peak = jitted grad phase + the
        # step's own double-buffered stream slot; mu/nu and the update
        # transient live host-side (memplan.offload_native_rows)
        step = make_train_step(cfg, mesh, state,
                               grad_accum=decl.grad_accum,
                               offload="optimizer")
        est = estimate(step.grad_phase, state.params, batch)
        peak = est.peak_bytes + step.stream_slot_bytes
        opt_resident_b = 0
    else:
        step = make_train_step(cfg, mesh, state,
                               grad_accum=decl.grad_accum)
        est = estimate(step, state, batch)
        peak = est.peak_bytes
        opt_resident_b = opt_b

    logits_b = decl.microbatch * model.max_seq_len * model.vocab_size * 4
    grads_b = params_b
    workspace_b = max(0, peak - params_b - grads_b - opt_resident_b
                      - logits_b)
    return {
        "peak_bytes": int(peak),
        "params_bytes": params_b,
        "grads_bytes": grads_b,
        "opt_state_bytes": opt_resident_b,
        "logits_bytes": logits_b,
        "workspace_bytes": workspace_b,
        "flops_per_step": float(est.flops),
        "seq": model.max_seq_len,
        "n_params": params_b // (2 if decl.param_dtype == "bfloat16"
                                 else 4),
    }


_cache: dict[str, dict] = {}
_cache_lock = make_lock("jaxcheck.pricer")


def _walk_cached(decl: DeclaredWorkload) -> dict:
    key = decl.key()
    with _cache_lock:
        hit = _cache.get(key)
    if hit is not None:
        return hit
    out = _walk(decl)
    with _cache_lock:
        _cache[key] = out
    return out


def cache_clear() -> None:
    with _cache_lock:
        _cache.clear()


def budget_bytes_per_chip(hbm_gib_per_chip: float) -> int:
    """Usable-HBM budget per chip, in bytes."""
    return int(hbm_gib_per_chip * USABLE_FRACTION * (2 ** 30))


def _binding_phase(walk: dict) -> str:
    """Which component binds the peak — the explanation's headline."""
    state_b = (walk["params_bytes"] + walk["grads_bytes"]
               + walk["opt_state_bytes"])
    parts = {"state (params+grads+optimizer)": state_b,
             "logits": walk["logits_bytes"],
             "backward workspace": walk["workspace_bytes"]}
    return max(parts, key=parts.get)


def price(decl: DeclaredWorkload, *, chips: int,
          hbm_gib_per_chip: float = CHIP_HBM_GIB) -> dict:
    """Price ``decl`` on a ``chips``-chip slice. Returns the admission
    verdict dict the webhook writes into the CR status."""
    walk = _walk_cached(decl)
    budget = budget_bytes_per_chip(hbm_gib_per_chip)
    per_chip = walk["peak_bytes"] / max(1, chips)
    fit = per_chip * (1 + HBM_MARGIN) <= budget
    binds = _binding_phase(walk)
    verdict = {
        "verdict": "fit" if fit else "rejected",
        "workload": decl.to_dict(),
        "chips": chips,
        "predicted_peak_gb": round(walk["peak_bytes"] / GB, 2),
        "predicted_peak_per_chip_gb": round(per_chip / GB, 2),
        "budget_per_chip_gb": round(budget / GB, 2),
        "hbm_margin": HBM_MARGIN,
        "binds": binds,
        "breakdown_gb": {
            "params": round(walk["params_bytes"] / GB, 2),
            "grads": round(walk["grads_bytes"] / GB, 2),
            "opt_state": round(walk["opt_state_bytes"] / GB, 2),
            "logits": round(walk["logits_bytes"] / GB, 2),
            "workspace": round(walk["workspace_bytes"] / GB, 2),
        },
        "flops_per_step": walk["flops_per_step"],
        "n_params": walk["n_params"],
        "tenant": decl.tenant,
    }
    verdict["explanation"] = (
        f"predicted peak {verdict['predicted_peak_per_chip_gb']} GB"
        f"/chip (x{chips} chips, {verdict['predicted_peak_gb']} GB "
        f"total) {'fits' if fit else 'exceeds'} the "
        f"{verdict['budget_per_chip_gb']} GB usable budget at a "
        f"{int(HBM_MARGIN * 100)}% allocator margin; "
        f"{binds} binds the peak")
    return verdict


# ---- the advisor -----------------------------------------------------

def _ladder(decl: DeclaredWorkload) -> list[DeclaredWorkload]:
    """Progressively cheaper rungs, least disruptive first. Each rung
    is a full declaration the user can paste back verbatim."""
    from dataclasses import replace

    rungs: list[DeclaredWorkload] = []

    def push(d: DeclaredWorkload) -> None:
        if d != decl and d not in rungs:
            rungs.append(d)

    cur = decl
    if cur.remat != "full":
        cur = replace(cur, remat="full")
        push(cur)
    # shrink the microbatch (batch stays: more accumulation steps)
    mb_rung = cur
    while mb_rung.microbatch > 1:
        mb_rung = replace(mb_rung, grad_accum=mb_rung.grad_accum * 2)
        if mb_rung.batch % mb_rung.grad_accum != 0:
            break
        push(mb_rung)
    # stream the optimizer update through host RAM
    off = replace(cur, offload="optimizer")
    push(off)
    off_mb = off
    while off_mb.microbatch > 1:
        off_mb = replace(off_mb, grad_accum=off_mb.grad_accum * 2)
        if off_mb.batch % off_mb.grad_accum != 0:
            break
        push(off_mb)
    return rungs[:8]   # bound the webhook's worst-case trace count


def advise(decl: DeclaredWorkload, *, chips: int,
           hbm_gib_per_chip: float = CHIP_HBM_GIB) -> dict | None:
    """The cheapest passing rung for a rejected declaration: the first
    ladder entry that fits, with its own priced verdict. None when no
    rung fits (the slice is simply too small)."""
    for rung in _ladder(decl):
        v = price(rung, chips=chips,
                  hbm_gib_per_chip=hbm_gib_per_chip)
        if v["verdict"] == "fit":
            return {
                "workload": rung.to_dict(),
                "predicted_peak_per_chip_gb":
                    v["predicted_peak_per_chip_gb"],
                "budget_per_chip_gb": v["budget_per_chip_gb"],
                "note": _advice_note(decl, rung),
            }
    return None


def _advice_note(decl: DeclaredWorkload, rung: DeclaredWorkload) -> str:
    changes = []
    if rung.remat != decl.remat:
        changes.append(f"remat={rung.remat}")
    if rung.grad_accum != decl.grad_accum:
        changes.append(f"grad_accum={rung.grad_accum} "
                       f"(microbatch {decl.microbatch}"
                       f"->{rung.microbatch})")
    if rung.offload != decl.offload:
        changes.append(f"offload={rung.offload}")
    return "cheapest passing rung: " + ", ".join(changes)
