"""Tensorboard logging for ``fit()`` — the training side of the
platform's TB story.

BASELINE.json's eval config 5 is "tensorboard-controller reading GCS
logs from TPU JAX run": the controller serves a Tensorboard CR over a
``gs://`` or ``pvc://`` path (``controllers/tensorboard.py``); THIS
callback is what writes those logs from inside the notebook. Point it
at the workspace PVC (``pvc://``) or a mounted GCS bucket and create a
Tensorboard CR over the same path from the tensorboards web app.

``tensorboardX`` is already in the jupyter-jax image requirements; the
import is deferred so the library stays optional elsewhere.
"""

from __future__ import annotations

from kubeflow_rm_tpu.training.loop import LoopMetrics


class TensorboardCallback:
    """``fit(callbacks=(TensorboardCallback(logdir),))`` — one scalar
    per LoopMetrics field per log interval, flushed eagerly so a
    Tensorboard server tailing the directory sees points live."""

    def __init__(self, logdir: str, *, flush_secs: int = 10):
        from tensorboardX import SummaryWriter

        self.writer = SummaryWriter(logdir, flush_secs=flush_secs)

    def __call__(self, m: LoopMetrics) -> None:
        self.writer.add_scalar("train/loss", m.loss, m.step)
        self.writer.add_scalar("train/grad_norm", m.grad_norm, m.step)
        self.writer.add_scalar("perf/tokens_per_sec", m.tokens_per_sec,
                               m.step)
        if m.mfu_pct is not None:
            self.writer.add_scalar("perf/mfu_pct", m.mfu_pct, m.step)
        self.writer.add_scalar("perf/step_time_ms", m.step_time_ms,
                               m.step)
        self.writer.flush()

    def close(self) -> None:
        self.writer.close()
