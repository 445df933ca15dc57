"""Llama-family decoder, written TPU-first in functional JAX.

Design notes (why this is not a torch translation):

- **Scan over layers.** All transformer blocks share one set of stacked
  weights with a leading layer axis and run under ``lax.scan``. XLA
  compiles a single block once instead of unrolling n_layers copies —
  compile time stays flat as depth grows, and the stacked layout gives
  every layer identical sharding, which is what the FSDP all-gather
  schedule wants.

- **Rematerialization.** ``jax.checkpoint`` wraps the scanned block with
  a dots-saveable policy: matmul outputs survive, attention scores and
  softmax are recomputed in the backward pass. This trades a ~30% FLOP
  overhead in attention for O(1) live layers of activation memory — the
  standard HBM/FLOPs trade on TPU.

- **bf16 compute, fp32 params/master.** Params are stored in
  ``param_dtype`` (fp32 by default) and cast to ``dtype`` (bf16) at use;
  the final logits come back in fp32 for the loss.

- Weights use a GPT-2-style scaled init (out-projections scaled by
  1/sqrt(2 * n_layers)) so tiny test configs train stably.

This model is the flagship for the jupyter-jax notebook image; the
platform half of the repo provisions the slice it runs on
(BASELINE.json north_star).
"""

from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from kubeflow_rm_tpu.ops import (
    apply_rope,
    dot_product_attention,
    rms_norm,
    rope_angles,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "dots": save all matmul outputs (fastest bwd, ~L× activation
    # memory); "full": save only the scan carry and recompute the block;
    # "attn"/"mlp"/"attn+mlp": save the named activations only (the
    # HBM-vs-recompute middle ground — see _NAME_POLICIES).
    remat_policy: str = "dots"
    # LoRA scaling (alpha/rank) for adapter-carrying params — see
    # models.lora; inert when no adapter leaves are present.
    lora_alpha: float = 16.0
    # "auto": dense attention, GSPMD inserts whatever collectives the
    # sp sharding needs (all-gather of K/V). "ring"/"ulysses": run the
    # explicit sequence-parallel schedule (parallel.ring_attention /
    # parallel.ulysses) when forward() is given a mesh with sp > 1 —
    # O(T/sp) attention memory per device instead of a gathered T.
    attention_backend: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def has_recurrent_state(self) -> bool:
        """Every layer's cache state is a strip of positions (compare
        ``models.nemotron_h``)."""
        return False

    # ---- presets -----------------------------------------------------
    @staticmethod
    def llama2_7b(**overrides) -> "LlamaConfig":
        return replace(LlamaConfig(), **overrides)

    @staticmethod
    def llama2_13b(**overrides) -> "LlamaConfig":
        return replace(
            LlamaConfig(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40,
                        hidden_dim=13824),
            **overrides,
        )

    @staticmethod
    def llama3_8b(**overrides) -> "LlamaConfig":
        return replace(
            LlamaConfig(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                        n_kv_heads=8, hidden_dim=14336, rope_theta=500000.0,
                        max_seq_len=8192),
            **overrides,
        )

    @staticmethod
    def bench_1b(**overrides) -> "LlamaConfig":
        """~1.2B-param config sized for a single v5e chip (16 GiB HBM)."""
        return replace(
            LlamaConfig(dim=2048, n_layers=20, n_heads=16, n_kv_heads=16,
                        hidden_dim=5632, max_seq_len=2048),
            **overrides,
        )

    @staticmethod
    def bench_2b(**overrides) -> "LlamaConfig":
        """~2.1B params: the mid rung of the single-chip MFU-vs-scale
        ladder (full fine-tune on one v5e with the factored optimizer —
        see bench.py --preset bench_2b --optim adafactor)."""
        return replace(
            LlamaConfig(dim=2560, n_layers=24, n_heads=20, n_kv_heads=20,
                        hidden_dim=6912, max_seq_len=2048),
            **overrides,
        )

    @staticmethod
    def bench_2_7b(**overrides) -> "LlamaConfig":
        """~2.7B params: one rung PAST the measured single-v5e wall —
        state (params+grads ≈ 10.8 GiB at 4 bytes/param) plus logits
        and recompute workspace OOMs 15.75 GiB usable HBM even at
        mb1/full remat (BENCH_SWEEP_r05 scale rows); bench_2b (~2.1B)
        is the largest full fine-tune that fits."""
        return replace(
            LlamaConfig(dim=3072, n_layers=22, n_heads=24, n_kv_heads=24,
                        hidden_dim=8192, max_seq_len=2048),
            **overrides,
        )

    @staticmethod
    def bench_3b(**overrides) -> "LlamaConfig":
        """~3.1B params: one rung PAST the single-v5e wall — state
        alone (params+grads ≈ 12.6 GiB) plus workspace/fragmentation
        exceeds 15.75 GiB usable HBM even at full remat (the OOM row
        in BENCH_SWEEP_r05); it exists to document the boundary and as
        the first multi-chip-ladder config."""
        return replace(
            LlamaConfig(dim=3072, n_layers=26, n_heads=24, n_kv_heads=24,
                        hidden_dim=8192, max_seq_len=2048),
            **overrides,
        )

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Test-sized config: runs in milliseconds on a CPU mesh."""
        return replace(
            LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, hidden_dim=128, max_seq_len=128,
                        dtype=jnp.float32),
            **overrides,
        )


#: named-tensor remat presets: save the listed activations, recompute
#: the rest in backward. Sizes per layer (B=4, T=2048, bench_1b):
#: qkv+attn 4x32 MB; mlp gate/up 92 MB each. "attn" skips recomputing
#: the attention pipeline (projections + rope + flash fwd) for ~1.9 GB;
#: "attn+mlp" also skips the two F-sized matmuls for ~3.7 GB more.
_NAME_POLICIES = {
    "attn": ("q_rope", "k_rope", "v_proj", "attn_out"),
    "attn+mlp": ("q_rope", "k_rope", "v_proj", "attn_out",
                 "mlp_gate", "mlp_up"),
    "mlp": ("mlp_gate", "mlp_up"),
}


def _remat_policy(name: str):
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    if name in _NAME_POLICIES:
        return jax.checkpoint_policies.save_only_these_names(
            *_NAME_POLICIES[name])
    raise ValueError(
        f"remat_policy must be one of "
        f"{sorted(['full', 'dots', *_NAME_POLICIES])}, got {name!r}")


def param_spec_shapes(cfg: LlamaConfig) -> dict:
    """Abstract shapes of the parameter pytree (layer-stacked)."""
    L, D, V = cfg.n_layers, cfg.dim, cfg.vocab_size
    H, KVH, hd, F = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.hidden_dim
    return {
        "embed": {"tokens": (V, D)},
        "blocks": {
            "attn_norm": (L, D),
            "wq": (L, D, H * hd),
            "wk": (L, D, KVH * hd),
            "wv": (L, D, KVH * hd),
            "wo": (L, H * hd, D),
            "mlp_norm": (L, D),
            "w_gate": (L, D, F),
            "w_up": (L, D, F),
            "w_down": (L, F, D),
        },
        "out_norm": (D,),
        "lm_head": (D, V),
    }


def init_params(cfg: LlamaConfig, key: jax.Array,
                shapes: dict | None = None) -> dict:
    """Random-init a parameter pytree matching ``param_spec_shapes``
    (or an explicit ``shapes`` tree — the MoE family passes its own)."""
    if shapes is None:
        shapes = param_spec_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(flat))
    leaves = [init_leaf(cfg, p[-1].key, s, k)
              for (p, s), k in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_leaf(cfg: LlamaConfig, name: str, shape, k: jax.Array):
    """Init rule for ONE named parameter leaf — the single source of
    truth shared by ``init_params`` and the leaf-at-a-time
    ``quantize.init_params_quantized`` (which must stay bit-identical
    to materialize-then-quantize)."""
    out_scale = 0.02 / (2.0 * cfg.n_layers) ** 0.5
    if "norm" in name:
        return jnp.ones(shape, cfg.param_dtype)
    if name in ("wo", "w_down", "moe_down"):  # residual-writing projections
        return (jax.random.normal(k, shape) * out_scale).astype(cfg.param_dtype)
    return (jax.random.normal(k, shape) * 0.02).astype(cfg.param_dtype)


def _attention_half(cfg: LlamaConfig, x, layer, cos, sin, positions,
                    segments, mesh=None):
    """Pre-norm attention + residual. x: (B, T, D) in compute dtype.

    Activations are tagged with ``checkpoint_name`` so remat policies
    can save exactly the tensors whose recompute is expensive relative
    to their HBM cost (see ``LlamaConfig.remat_policy``). Shared with
    the MoE family (``models.mixtral``), whose blocks differ only in
    the FFN half."""
    from jax.ad_checkpoint import checkpoint_name

    B, T, D = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.dtype

    from kubeflow_rm_tpu.models.lora import lora_proj

    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    proj = partial(lora_proj, layer, alpha=cfg.lora_alpha, dtype=cdt)
    q = proj("wq", h).reshape(B, T, H, hd)
    k = proj("wk", h).reshape(B, T, KVH, hd)
    v = proj("wv", h).reshape(B, T, KVH, hd)
    q = checkpoint_name(apply_rope(q, cos, sin), "q_rope")
    k = checkpoint_name(apply_rope(k, cos, sin), "k_rope")
    v = checkpoint_name(v, "v_proj")
    backend = cfg.attention_backend
    if backend not in ("auto", "ring", "ulysses"):
        raise ValueError(
            f"attention_backend must be auto/ring/ulysses, got {backend!r}")
    if (backend != "auto" and mesh is not None
            and mesh.shape.get("sp", 1) > 1):
        if backend == "ring":
            from kubeflow_rm_tpu.parallel.ring_attention import (
                ring_self_attention,
            )
            attn = ring_self_attention(q, k, v, mesh, causal=True,
                                       positions=positions,
                                       segments=segments)
        else:
            from kubeflow_rm_tpu.parallel.ulysses import (
                ulysses_self_attention,
            )
            attn = ulysses_self_attention(q, k, v, mesh, causal=True,
                                          positions=positions,
                                          segments=segments)
    else:
        attn = dot_product_attention(
            q, k, v, causal=True, positions_q=positions,
            positions_kv=positions,
            segment_ids_q=segments, segment_ids_kv=segments,
            mesh=mesh,
        )
    attn = checkpoint_name(attn, "attn_out")
    return x + proj("wo", attn.reshape(B, T, H * hd))


def _block(cfg: LlamaConfig, x, layer, cos, sin, positions, segments,
           mesh=None):
    """One transformer block (attention + dense SwiGLU MLP)."""
    from jax.ad_checkpoint import checkpoint_name

    cdt = cfg.dtype
    from kubeflow_rm_tpu.models.lora import lora_proj

    x = _attention_half(cfg, x, layer, cos, sin, positions, segments,
                        mesh=mesh)
    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    proj = partial(lora_proj, layer, alpha=cfg.lora_alpha, dtype=cdt)
    gate = checkpoint_name(proj("w_gate", h), "mlp_gate")
    up = checkpoint_name(proj("w_up", h), "mlp_up")
    x = x + proj("w_down", jax.nn.silu(gate) * up)
    return x


def _prologue(params, tokens, cfg: LlamaConfig, positions, segments,
              packed: bool, mesh=None):
    """Shared forward prologue: the positions/packed mask contract,
    embedding gather, rope tables, and the remat-wrapped block. Used by
    both the plain ``forward`` and ``parallel.pipeline`` so the two
    execution schedules cannot drift."""
    B, T = tokens.shape
    if positions is None or packed:
        attn_positions = None
    else:
        attn_positions = positions
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    # gather the (B, T, D) rows first, then cast — never materialize a
    # compute-dtype copy of the whole (V, D) table
    x = params["embed"]["tokens"][tokens].astype(cfg.dtype)
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    block = partial(_block, cfg, mesh=mesh)
    if cfg.remat:
        block = jax.checkpoint(block, policy=_remat_policy(cfg.remat_policy))
    return x, cos, sin, attn_positions, block


def _epilogue(params, x, cfg: LlamaConfig) -> jax.Array:
    """Shared forward epilogue: final norm, lm head, fp32 logits."""
    from kubeflow_rm_tpu.models.quantize import maybe_dequant

    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = x @ maybe_dequant(params["lm_head"], cfg.dtype)
    return logits.astype(jnp.float32)


def forward(
    params: dict,
    tokens: jax.Array,
    cfg: LlamaConfig,
    positions: jax.Array | None = None,
    segments: jax.Array | None = None,
    *,
    packed: bool = False,
    mesh=None,
) -> jax.Array:
    """Causal LM forward pass.

    Args:
      params: pytree from ``init_params``.
      tokens: (B, T) int32 token ids.
      positions: (B, T) global positions; defaults to arange. Passing
        explicit positions is how sequence-parallel shards and packed
        sequences get correct RoPE.
      segments: (B, T) document segment ids for packed sequences (from
        ``training.data.pack_documents``); restricts attention to equal
        segments so packed documents stay independent.
      packed: assert that ``positions`` restart per document and are
        monotone within each segment (the ``pack_documents`` layout).
        Only then may the attention mask drop positions — local-causal
        ∧ same-segment is exact for that layout, and leaving
        attn_positions=None keeps the call on the pallas flash kernel.
        Without the flag, explicit positions + segments (e.g. a zigzag
        sequence-parallel shard of packed data, whose positions are
        NON-monotonic) keep the position-aware XLA path — silently
        assuming monotonicity would compute a wrong mask.

    Returns:
      (B, T, vocab) fp32 logits.
    """
    x, cos, sin, attn_positions, block = _prologue(
        params, tokens, cfg, positions, segments, packed, mesh=mesh)

    def scan_body(x, layer):
        return block(x, layer, cos, sin, attn_positions, segments), None

    x, _ = jax.lax.scan(scan_body, x, params["blocks"])
    return _epilogue(params, x, cfg)
