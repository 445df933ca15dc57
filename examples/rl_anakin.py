#!/usr/bin/env python3
"""Anakin-style actor–learner RL driven through the platform as a TPUJob.

Podracer (arxiv 2104.06272) describes two TPU RL architectures; Anakin
is the one where the learner owns the accelerator and actors are cheap
CPU processes feeding it trajectories. This example runs that shape
END-TO-END through the control plane — not as a hand-wired script:

1. boot the in-process platform (``make_control_plane`` + a small TPU
   node fleet) — the same stack the conformance walks drive;
2. submit a ``TPUJob`` CR: one ``learner`` role on a TPU slice plus N
   CPU-only ``actors`` — the whole gang binds all-or-nothing through
   ``SchedulerCache.gang_bind``;
3. verify the gang came up Running and every pod carries the role
   rendezvous env the webhook injected (``TPU_JOB_ROLE``,
   ``TPU_JOB_ROLE_INDEX``, ``TPU_JOB_LEARNER_ADDRESS``);
4. run the RL loop with the platform's API as the transport, the way
   the real pods would use the REST facade: the learner broadcasts
   params as a versioned ConfigMap, actors post trajectory ConfigMaps,
   the learner consumes them and applies a jitted REINFORCE update
   over a ``parallel/mesh.py`` mesh.

The toy problem is a 5-armed bandit: the exact expected loss
``-(softmax(logits) · rewards)`` is computable in closed form, so the
dryrun can assert learning happened (finite, decreasing loss) without
statistical slack.

Dryrun smoke (CPU mesh — what CI runs):
    JAX_PLATFORMS=cpu python examples/rl_anakin.py --dryrun --steps 20
"""

from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

#: per-arm expected rewards of the toy bandit; arm 2 is optimal, so a
#: learning policy drives the loss toward -0.9
TRUE_REWARDS = (0.1, 0.4, 0.9, 0.2, 0.5)


# ---- platform side ---------------------------------------------------

def boot_platform(num_nodes: int, accel: str):
    """The in-process stack: apiserver + every controller + webhook +
    a fleet of TPU nodes (one per host of ``num_nodes`` slices)."""
    from kubeflow_rm_tpu.controlplane import make_control_plane
    from kubeflow_rm_tpu.controlplane.api import tpu as tpu_api
    from kubeflow_rm_tpu.controlplane.controllers.statefulset import (
        make_tpu_node,
    )
    api, mgr = make_control_plane()
    api.ensure_namespace("rl")
    topo = tpu_api.lookup(accel)
    for i in range(num_nodes * topo.hosts):
        api.create(make_tpu_node(f"tpu-{i}", accel))
    return api, mgr


def submit_job(api, mgr, *, name: str, actors: int, accel: str) -> dict:
    """Create the TPUJob CR, reconcile to steady state, and assert the
    gang contract held: phase Running, every pod bound, role env on
    chip pods AND actors (TPU env only on chip pods)."""
    from kubeflow_rm_tpu.controlplane.api import tpujob as tj_api
    job = tj_api.make_tpujob(name, "rl", roles=[
        {"name": "learner", "replicas": 1,
         "tpu": {"acceleratorType": accel}},
        {"name": "actors", "replicas": actors, "cpu": "1"},
    ])
    api.create(job)
    mgr.run_until_idle()
    live = api.get(tj_api.KIND, name, "rl")
    status = live.get("status") or {}
    if status.get("phase") != tj_api.RUNNING_PHASE:
        raise SystemExit(f"gang failed to assemble: status={status}")
    pods = api.list("Pod", "rl",
                    {"matchLabels": {tj_api.JOB_NAME_LABEL: name}})
    for p in pods:
        env = {e["name"]: e.get("value")
               for c in p["spec"]["containers"]
               for e in c.get("env", [])}
        role = env.get(tj_api.ENV_JOB_ROLE)
        assert role in ("learner", "actors"), p["metadata"]["name"]
        assert env.get(tj_api.ENV_LEARNER_ADDRESS), "no learner address"
        is_chip = "TPU_WORKER_ID" in env
        assert is_chip == (role == "learner"), (
            f"{p['metadata']['name']}: TPU env on a CPU actor (or "
            "missing on a chip pod)")
    return status


# ---- RL side (the toy Anakin loop) -----------------------------------

def _publish_params(api, logits, version: int) -> None:
    """Learner → actors broadcast, as the pods would do it: a versioned
    ConfigMap the actors poll (pull model — the in-memory apiserver
    and the REST facade serve the same verb)."""
    body = {"apiVersion": "v1", "kind": "ConfigMap",
            "metadata": {"name": "anakin-params", "namespace": "rl"},
            "data": {"logits": json.dumps([float(x) for x in logits]),
                     "version": str(version)}}
    try:
        cur = api.get("ConfigMap", "anakin-params", "rl")
        cur["data"] = body["data"]
        api.update(cur)
    except Exception:
        api.create(body)


def _fetch_params(api):
    cm = api.get("ConfigMap", "anakin-params", "rl")
    import numpy as np
    return (np.asarray(json.loads(cm["data"]["logits"])),
            int(cm["data"]["version"]))


def _post_trajectory(api, actor: int, step: int, actions, rewards):
    api.create({
        "apiVersion": "v1", "kind": "ConfigMap",
        "metadata": {"name": f"anakin-traj-{actor}-{step}",
                     "namespace": "rl",
                     "labels": {"app": "anakin-traj",
                                "step": str(step)}},
        "data": {"actions": json.dumps([int(a) for a in actions]),
                 "rewards": json.dumps([float(r) for r in rewards])},
    })


def _drain_trajectories(api, step: int):
    out = []
    for cm in api.list("ConfigMap", "rl",
                       {"matchLabels": {"app": "anakin-traj",
                                        "step": str(step)}}):
        out.append((json.loads(cm["data"]["actions"]),
                    json.loads(cm["data"]["rewards"])))
        api.delete("ConfigMap", cm["metadata"]["name"], "rl")
    return out


def run_loop(api, *, actors: int, steps: int, batch: int,
             lr: float, seed: int) -> list[float]:
    """The Anakin cycle: broadcast → act → learn, ``steps`` times.

    The learner update is REINFORCE with a mean-reward baseline,
    jitted once over the framework mesh (dp×fsdp over however many
    devices the platform gave us — on CPU that is a 1×1 mesh, on a
    real slice the same code spans the chips)."""
    import jax
    import jax.numpy as jnp

    from kubeflow_rm_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig())
    n_arms = len(TRUE_REWARDS)
    true_r = jnp.asarray(TRUE_REWARDS)

    @jax.jit
    def update(logits, actions, rewards):
        def neg_score(lg):
            logp = jax.nn.log_softmax(lg)
            baseline = rewards.mean()
            return -jnp.mean((rewards - baseline) * logp[actions])
        grads = jax.grad(neg_score)(logits)
        return logits - lr * grads

    @jax.jit
    def exact_loss(logits):
        # closed-form expected negative reward of the current policy —
        # the assertable learning signal (no sampling noise)
        return -jnp.dot(jax.nn.softmax(logits), true_r)

    key = jax.random.PRNGKey(seed)
    logits = jnp.zeros(n_arms)
    _publish_params(api, logits, 0)
    losses: list[float] = []
    with mesh:
        for step in range(steps):
            # actors: pull params, sample a batch, post trajectories
            for a in range(actors):
                pulled, _ = _fetch_params(api)
                key, sub = jax.random.split(key)
                acts = jax.random.categorical(
                    sub, jnp.asarray(pulled), shape=(batch,))
                key, sub = jax.random.split(key)
                rews = (true_r[acts]
                        + 0.05 * jax.random.normal(sub, (batch,)))
                _post_trajectory(api, a, step, list(acts), list(rews))
            # learner: drain the step's trajectories, one fused update
            trajs = _drain_trajectories(api, step)
            assert len(trajs) == actors, "lost trajectories in flight"
            acts = jnp.asarray(sum((t[0] for t in trajs), []))
            rews = jnp.asarray(sum((t[1] for t in trajs), []))
            logits = update(logits, acts, rews)
            _publish_params(api, logits, step + 1)
            losses.append(float(exact_loss(logits)))
    return losses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true",
                    help="CPU smoke: assert the loss is finite and "
                         "decreasing, print a JSON summary")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--actors", type=int, default=4)
    ap.add_argument("--batch", type=int, default=64,
                    help="samples per actor per step")
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--accel", default="v5p-16")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    api, mgr = boot_platform(1, args.accel)
    status = submit_job(api, mgr, name="anakin", actors=args.actors,
                        accel=args.accel)
    print(f"gang Running: {status['readyPods']}/{status['totalPods']} "
          f"pods ({json.dumps(status['roles'])})")

    losses = run_loop(api, actors=args.actors, steps=args.steps,
                      batch=args.batch, lr=args.lr, seed=args.seed)
    import math
    summary = {
        "steps": args.steps,
        "actors": args.actors,
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "optimal_loss": -max(TRUE_REWARDS),
        "finite": all(math.isfinite(x) for x in losses),
        "decreased": losses[-1] < losses[0],
    }
    print(json.dumps(summary))
    if args.dryrun:
        assert summary["finite"], "non-finite loss"
        assert summary["decreased"], (
            f"loss did not decrease: {losses[0]} -> {losses[-1]}")
        print("dryrun OK: loss", round(losses[0], 4), "->",
              round(losses[-1], 4))
    return 0


if __name__ == "__main__":
    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
