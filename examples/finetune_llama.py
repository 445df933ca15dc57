#!/usr/bin/env python3
"""Fine-tune a Llama on the slice this notebook was spawned with.

The end-to-end in-notebook workflow the whole platform exists to serve
(SURVEY.md §7's final conformance artifact), usable as a script or
pasted cell-by-cell into a jupyter-jax notebook:

1. join the slice — the webhook-injected rendezvous env
   (``TPU_WORKER_ID``/``TPU_WORKER_HOSTNAMES``) becomes one
   ``jax.distributed`` job;
2. build the mesh (fsdp × tp over however many chips showed up);
3. load weights — an HF checkpoint via ``from_hf_llama``, or a preset;
4. stream packed batches from jsonl shards, host-disjoint;
5. ``fit()`` with gradient accumulation, orbax checkpointing, live MFU;
6. sample a continuation and (optionally) export back to HF format.

Tiny smoke (CPU mesh, synthetic data — what tests/test_examples.py
runs):   python examples/finetune_llama.py --preset tiny --steps 4
Real slice (v5p-8 north star):
    python examples/finetune_llama.py --preset llama2_7b \
        --hf-model meta-llama/Llama-2-7b-hf --data 'gs://bucket/*.jsonl' \
        --batch 8 --grad-accum 4 --seq-len 4096 --fsdp 4 --tp 2
"""

from __future__ import annotations

import argparse
import glob
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny",
                    help="LlamaConfig preset (tiny/bench_1b/llama2_7b/...)")
    ap.add_argument("--hf-model", default=None,
                    help="HF model id/path to load weights from")
    ap.add_argument("--data", default=None,
                    help="glob of pre-tokenized jsonl shards "
                         "(default: synthetic)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=0,
                    help="0 = all local devices")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--lora-rank", type=int, default=0,
                    help="train rank-r adapters instead of full "
                         "fine-tuning (frozen base: no grads/moments)")
    qbase = ap.add_mutually_exclusive_group()
    qbase.add_argument("--int8-base", action="store_true",
                       help="with --lora-rank: quantize the frozen "
                            "base to int8 (the 7B-on-one-v5e recipe)")
    qbase.add_argument("--int4-base", action="store_true",
                       help="with --lora-rank: pack the frozen base "
                            "to int4 (~3.6 GB for 7B — the "
                            "QLoRA-style maximum-headroom recipe)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--tb-logdir", default=None,
                    help="write tensorboard events here (point a "
                         "Tensorboard CR at the same pvc:// path)")
    ap.add_argument("--export-hf", default=None,
                    help="write the tuned weights as an HF state_dict "
                         "(.npz) here")
    ap.add_argument("--sample", default=True, action=argparse.
                    BooleanOptionalAction,
                    help="greedy-decode a continuation at the end")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from kubeflow_rm_tpu.models import LlamaConfig, generate_fused
    from kubeflow_rm_tpu.parallel import MeshConfig, make_mesh
    from kubeflow_rm_tpu.parallel.distributed import initialize
    from kubeflow_rm_tpu.training import TrainConfig
    from kubeflow_rm_tpu.training.optim import OptimConfig
    from kubeflow_rm_tpu.training.data import (
        device_prefetch, jsonl_documents, packed_batches,
        synthetic_batches,
    )
    from kubeflow_rm_tpu.training.loop import LoopConfig, fit
    from kubeflow_rm_tpu.training.train import TrainState

    # 1. the slice: no-op on single-host; multi-host pods all run this
    env = initialize()
    devices = jax.devices()
    fsdp = args.fsdp or max(1, len(devices) // (args.dp * args.tp))
    mesh = make_mesh(MeshConfig(dp=args.dp, fsdp=fsdp, tp=args.tp),
                     devices[:args.dp * fsdp * args.tp])
    print(f"process {env.process_id}/{env.num_hosts} "
          f"mesh {dict(mesh.shape)}")

    # 2. the model
    if args.hf_model:
        import transformers

        from kubeflow_rm_tpu.models import from_hf_llama
        hf = transformers.LlamaForCausalLM.from_pretrained(args.hf_model)
        model_cfg, params = from_hf_llama(hf)
    else:
        model_cfg = getattr(LlamaConfig, args.preset)()
        params = None
    optim = OptimConfig(train_only="lora" if args.lora_rank else None)
    cfg = TrainConfig(model=model_cfg, optim=optim)
    state = None  # built below once params are final
    if args.lora_rank:
        from kubeflow_rm_tpu.models import add_lora, init_params
        bits = 4 if args.int4_base else 8
        if params is None and (args.int8_base or args.int4_base):
            # no checkpoint: build the base DIRECTLY in quantized form,
            # leaf by leaf — a 7B's full-precision copy never fits next
            # to its quantized one on a 16 GiB chip
            from kubeflow_rm_tpu.models.quantize import (
                init_params_quantized,
            )
            params = init_params_quantized(model_cfg, jax.random.key(0),
                                           bits=bits)
        else:
            if params is None:
                params = init_params(model_cfg, jax.random.key(0))
            if args.int8_base or args.int4_base:
                from kubeflow_rm_tpu.models import quantize_params
                params = quantize_params(params, bits=bits)
        params = add_lora(params, args.lora_rank, key=jax.random.key(1))

    # 3. the data
    if args.data:
        paths = sorted(glob.glob(args.data))
        docs = jsonl_documents(paths, process_id=env.process_id,
                               num_processes=env.num_hosts, seed=0)
        batches = device_prefetch(
            packed_batches(docs, args.batch, args.seq_len), mesh)
        batch_keys = ("tokens", "labels", "positions", "segments")
    else:
        batches = synthetic_batches(args.batch, args.seq_len,
                                    cfg.model.vocab_size)
        batch_keys = ("tokens", "labels")

    # 4. train (fit restores from checkpoint_dir when present)
    if params is not None:
        from kubeflow_rm_tpu.training.train import init_train_state
        state = init_train_state(cfg, jax.random.key(0), params=params)
    loop = LoopConfig(total_steps=args.steps,
                      log_every=max(1, args.steps // 10),
                      checkpoint_dir=args.checkpoint_dir,
                      grad_accum=args.grad_accum)
    callbacks = ()
    if args.tb_logdir and env.process_id == 0:
        from kubeflow_rm_tpu.utils.tensorboard import TensorboardCallback
        callbacks = (TensorboardCallback(args.tb_logdir),)
    state, history = fit(cfg, mesh, batches, loop, state=state,
                         batch_keys=batch_keys, callbacks=callbacks)
    if history:
        last = history[-1]
        mfu = ("n/a (no TPU)" if last.mfu_pct is None
               else f"{last.mfu_pct:.1f}%")
        print(f"final: step {last.step} loss {last.loss:.4f} "
              f"{last.tokens_per_sec:.0f} tok/s mfu {mfu}")

    # 5. sample — decode applies adapters and int8 bases directly
    if args.sample and env.process_id == 0:
        prompt = np.ones((1, 4), np.int32)
        out = generate_fused(state.params, cfg.model,
                             jax.numpy.asarray(prompt), max_new_tokens=8)
        print("sample token ids:", np.asarray(out)[0].tolist())

    # 6. export
    if args.export_hf and env.process_id == 0:
        from kubeflow_rm_tpu.models.convert import to_hf_llama
        np.savez(args.export_hf, **to_hf_llama(cfg.model, state.params))
        print(f"exported HF state_dict -> {args.export_hf}")
    return 0


if __name__ == "__main__":
    from kubeflow_rm_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
