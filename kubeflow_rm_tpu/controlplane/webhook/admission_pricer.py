"""Predictive admission: the memplan walker in the webhook path.

A Notebook or TPUJob that declares its training workload
(``tpu.kubeflow.org/declared-workload`` — preset or explicit model
dims plus optim/batch/accum/remat/seq/offload knobs) gets priced by
:mod:`kubeflow_rm_tpu.analysis.jaxcheck.pricer` **at admission**, before
any placement:

- the verdict (predicted peak vs the slice's HBM budget, which phase
  binds, the full breakdown) lands in ``status.admission``;
- the predicted slice HBM and FLOPs are stamped as annotations the
  controllers fan out per-pod, giving the scheduler its second packing
  axis;
- a config whose predicted peak exceeds the budget is marked
  ``verdict: rejected`` — the Notebook/TPUJob controllers refuse to
  render pods for it (rejected *before placement*), and the
  **advisor** writes the cheapest passing rung from the memplan ladder
  into the status so the user can fix the config without a single
  OOMed step;
- a declaration that fails to parse NEVER rejects: the webhook
  degrades to chip-count-only admission with a ``Warning`` event and a
  ``swallowed_errors_total`` increment (an annotation typo must not
  take down the create path).

The CR itself is always admitted — a rejected verdict must live
somewhere the user and the advisor can see, and a denied CREATE leaves
no object to carry it. "Rejected" therefore means: status says so, an
event says why, and no pod ever renders until an UPDATE reprices the
declaration to a fitting rung.
"""

from __future__ import annotations

import json

from kubeflow_rm_tpu.controlplane.api import notebook as nb_api
from kubeflow_rm_tpu.controlplane.api import tpu as tpu_api
from kubeflow_rm_tpu.controlplane.api import tpujob as tj_api
from kubeflow_rm_tpu.controlplane.api.meta import (
    annotations_of,
    deep_get,
    fast_deepcopy,
    name_of,
    namespace_of,
)
from kubeflow_rm_tpu.controlplane.apiserver import APIServer


def record_declared_drift(agreement) -> float:
    """Bridge ``memplan_agreement`` rows (the native walk of the
    shipped step vs what the pricer predicted from the declaration)
    into the metrics registry as the worst absolute delta ratio. The
    Observer's TSDB samples the gauge every tick and the warn-only
    ``declared-hbm-drift`` SLO surfaces a sustained >20% divergence at
    ``/api/alerts``. Flag-only by design: drift means the declared
    HBM axis the scheduler packs on is lying, so the operator repacks
    (reprices) before the next bind — nothing here pages or preempts.
    Returns the ratio it recorded."""
    from kubeflow_rm_tpu.controlplane import metrics

    worst = 0.0
    for row in agreement or ():
        declared = row.get("priced_on_chip_peak_gb")
        observed = row.get("native_on_chip_peak_gb")
        if declared:
            worst = max(worst, abs(observed - declared) / declared)
        elif row.get("delta_pct") is not None:
            worst = max(worst, abs(row["delta_pct"]) / 100.0)
    metrics.DECLARED_HBM_DRIFT_RATIO.set(worst)
    return worst


def slice_topology_of(obj: dict) -> tpu_api.SliceTopology | None:
    """The slice the declared workload would run on: a Notebook's
    ``spec.tpu``, or a TPUJob's first TPU role (the learner — the role
    the model lives on)."""
    if obj.get("kind") == nb_api.KIND:
        try:
            return nb_api.tpu_spec(obj)
        except tpu_api.UnknownAcceleratorType:
            return None
    if obj.get("kind") == tj_api.KIND:
        learner = tj_api.learner_role(tj_api.roles(obj))
        acc = learner and tj_api.role_accelerator(learner)
        if acc:
            try:
                return tpu_api.lookup(acc)
            except tpu_api.UnknownAcceleratorType:
                return None
    return None


def admission_status(obj: dict) -> dict | None:
    """The priced verdict the webhook stamped, if any."""
    adm = deep_get(obj, "status", "admission")
    return adm if isinstance(adm, dict) else None


def is_admission_rejected(obj: dict) -> bool:
    adm = admission_status(obj)
    return bool(adm and adm.get("verdict") == "rejected")


class AdmissionPricer:
    """Prices declared workloads on Notebook and TPUJob CREATE/UPDATE."""

    def __init__(self, api: APIServer):
        self.api = api

    def register(self) -> None:
        self.api.register_admission(nb_api.KIND, self)
        self.api.register_admission(tj_api.KIND, self)

    def __call__(self, op: str, obj: dict,
                 old: dict | None) -> dict | None:
        if op not in ("CREATE", "UPDATE"):
            return None
        declared = annotations_of(obj).get(
            tpu_api.DECLARED_WORKLOAD_ANNOTATION)
        if not declared:
            # declaration removed: drop the stale verdict so a
            # previously-rejected CR isn't gated forever
            if admission_status(obj) is not None:
                obj = fast_deepcopy(obj)
                self._clear(obj)
                return obj
            return None
        topo = slice_topology_of(obj)
        if topo is None:
            return None   # CPU workload: nothing to price against
        obj = fast_deepcopy(obj)
        try:
            self._price(op, obj, old, declared, topo)
        except (AttributeError, ImportError):
            # a broken pricer (a jax name that moved, a missing module)
            # is our bug, not the tenant's declaration: degrading here
            # would switch HBM admission off without a word
            raise
        except Exception as e:
            # satellite bugfix contract: an unparseable (or untraceable)
            # declaration degrades to chip-count-only admission —
            # warning + counter, never a reject, never a crash
            self._clear(obj)
            if old is None or annotations_of(old).get(
                    tpu_api.DECLARED_WORKLOAD_ANNOTATION) != declared:
                # warn once per distinct bad declaration, not on every
                # status-mirror UPDATE that re-runs admission
                from kubeflow_rm_tpu.controlplane import metrics
                metrics.swallowed("admission",
                                  "declared-workload pricing")
                try:
                    self.api.record_event(
                        obj, "Warning", "DeclaredWorkloadUnparseable",
                        f"cannot price "
                        f"{tpu_api.DECLARED_WORKLOAD_ANNOTATION}: {e};"
                        f" admitting on chip count only")
                except Exception:
                    metrics.swallowed("admission", "unparseable event")
        return obj

    # -- internals -----------------------------------------------------

    def _price(self, op: str, obj: dict, old: dict | None,
               declared: str, topo: tpu_api.SliceTopology) -> None:
        from kubeflow_rm_tpu.analysis.jaxcheck import pricer

        decl = pricer.parse(declared)
        verdict = pricer.price(decl, chips=topo.chips,
                               hbm_gib_per_chip=topo.hbm_gib_per_chip)
        verdict["accelerator_type"] = topo.accelerator_type
        if verdict["verdict"] == "rejected":
            advice = pricer.advise(
                decl, chips=topo.chips,
                hbm_gib_per_chip=topo.hbm_gib_per_chip)
            verdict["advisor"] = advice  # None: no rung fits the slice
        obj.setdefault("status", {})["admission"] = verdict
        ann = obj["metadata"].setdefault("annotations", {})
        ann[tpu_api.PREDICTED_HBM_ANNOTATION] = str(
            verdict["predicted_peak_gb"])
        ann[tpu_api.PREDICTED_FLOPS_ANNOTATION] = str(
            verdict["flops_per_step"])
        if verdict["verdict"] == "rejected" and self._newly_rejected(
                obj, old, declared):
            advice = verdict.get("advisor")
            hint = (f"; advisor: {advice['note']} -> "
                    f"{json.dumps(advice['workload'], sort_keys=True)}"
                    if advice else
                    "; no ladder rung fits this slice — use a larger "
                    "accelerator")
            self.api.record_event(
                obj, "Warning", "AdmissionRejected",
                f"{obj['kind']} {namespace_of(obj)}/{name_of(obj)}: "
                f"{verdict['explanation']}{hint}")

    def _newly_rejected(self, obj: dict, old: dict | None,
                        declared: str) -> bool:
        """Emit the rejection event once per distinct declaration, not
        on every status-mirror UPDATE that flows through admission."""
        if old is None:
            return True
        old_declared = annotations_of(old).get(
            tpu_api.DECLARED_WORKLOAD_ANNOTATION)
        return old_declared != declared or not is_admission_rejected(old)

    @staticmethod
    def _clear(obj: dict) -> None:
        status = obj.get("status")
        if isinstance(status, dict):
            status.pop("admission", None)
        ann = obj["metadata"].get("annotations")
        if ann:
            ann.pop(tpu_api.PREDICTED_HBM_ANNOTATION, None)
            ann.pop(tpu_api.PREDICTED_FLOPS_ANNOTATION, None)
