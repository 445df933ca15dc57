"""fit() → tensorboard events → the Tensorboard CR path contract.

The full BASELINE config-5 story in one hermetic test: a training run
writes TB event files into a workspace directory, and a Tensorboard CR
pointed at the same path (``pvc://``) renders a Deployment mounting it
— the platform and compute halves meeting over the log directory.
"""

from pathlib import Path

import jax
import pytest

from kubeflow_rm_tpu.models import LlamaConfig
from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
from kubeflow_rm_tpu.training import TrainConfig
from kubeflow_rm_tpu.training.data import synthetic_batches
from kubeflow_rm_tpu.training.loop import LoopConfig, fit
from kubeflow_rm_tpu.utils.tensorboard import TensorboardCallback


def test_fit_writes_tensorboard_events(tmp_path, devices8):
    pytest.importorskip("tensorboardX")
    cfg = TrainConfig(model=LlamaConfig.tiny())
    mesh = make_mesh(MeshConfig(fsdp=4), devices8[:4])
    cb = TensorboardCallback(str(tmp_path / "logs"))
    _, history = fit(
        cfg, mesh, synthetic_batches(4, 32, cfg.model.vocab_size),
        LoopConfig(total_steps=4, log_every=2), callbacks=(cb,))
    cb.close()
    assert history
    events = list((tmp_path / "logs").glob("events.out.tfevents.*"))
    assert events and events[0].stat().st_size > 0

    # the written scalar tags survive in the event file
    raw = events[0].read_bytes()
    assert b"train/loss" in raw and b"perf/tokens_per_sec" in raw
    # MFU is a chip metric: absent on the CPU mesh, not written as 0
    assert b"perf/mfu_pct" not in raw


def test_tensorboard_cr_serves_the_same_path(tmp_path):
    """A Tensorboard CR over the workspace PVC path mounts the PVC the
    training wrote into (ref tensorboard_controller.go:178-232)."""
    from kubeflow_rm_tpu.controlplane import make_control_plane
    from kubeflow_rm_tpu.controlplane.api.meta import deep_get, make_object

    api, mgr = make_control_plane()
    api.ensure_namespace("team")
    pvc = make_object("v1", "PersistentVolumeClaim", "nb-workspace",
                      "team")
    pvc["spec"] = {"resources": {"requests": {"storage": "5Gi"}},
                   "accessModes": ["ReadWriteOnce"]}
    api.create(pvc)
    tb = make_object("tensorboard.kubeflow.org/v1alpha1", "Tensorboard",
                     "train-logs", "team",
                     spec={"logspath": "pvc://nb-workspace/logs"})
    api.create(tb)
    mgr.enqueue_all()
    mgr.run_until_idle()

    deploy = api.get("Deployment", "train-logs", "team")
    spec = deep_get(deploy, "spec", "template", "spec")
    claims = [deep_get(v, "persistentVolumeClaim", "claimName")
              for v in spec.get("volumes", [])]
    assert "nb-workspace" in claims
    args = " ".join(spec["containers"][0].get("command", []) +
                    spec["containers"][0].get("args", []))
    assert "logs" in args
