"""Model-FLOPs accounting and MFU.

MFU = (model FLOPs/sec achieved) / (chip peak bf16 FLOPs/sec), with
model FLOPs counted by the standard convention (PaLM appendix B /
scaling-book): 6 FLOPs per matmul parameter per trained token
(fwd 2 + bwd 4), plus the attention score/value matmuls
(12·L·H·hd·T per token, halved for causal), and **not** counting
rematerialization recompute — remat makes the hardware do more work,
it does not make the model bigger.

The reference platform has no FLOPs accounting anywhere (SURVEY.md §6:
no published benchmarks); this module is what turns the north-star
"≥40% MFU on a TPU slice" (BASELINE.md) into a measured number.
"""

from kubeflow_rm_tpu.models.llama import LlamaConfig

# chip peak dense bf16 FLOPs/sec by device kind substring (public specs)
_PEAK_BF16 = (
    ("v6", 918e12),      # Trillium / v6e
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # jax device_kind for v5e
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def device_peak_flops(device) -> float | None:
    """Peak dense bf16 FLOPs/sec for a jax device. None off-TPU, where
    there is no MFU to report; a TPU kind missing from the table is an
    error, not a default."""
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for sub, peak in _PEAK_BF16:
        if sub in kind:
            return peak
    raise ValueError(
        f"no peak bf16 FLOP/s on record for TPU device_kind "
        f"{device.device_kind!r}; add it to utils.flops._PEAK_BF16")


def matmul_param_count(cfg: LlamaConfig) -> int:
    """Parameters that take part in matmuls (excludes the embedding
    gather and the vector norm gains)."""
    L, D, V = cfg.n_layers, cfg.dim, cfg.vocab_size
    H, KVH, hd, F = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.hidden_dim
    per_layer = D * H * hd + 2 * D * KVH * hd + H * hd * D + 3 * D * F
    return L * per_layer + D * V  # + lm_head


def train_flops_per_token(cfg: LlamaConfig, seq_len: int,
                          causal: bool = True,
                          frozen_base: bool = False) -> float:
    """Model FLOPs per trained token for one fwd+bwd step.

    ``frozen_base=True`` (LoRA/QLoRA): the base weights take no
    weight-gradient matmuls, so each matmul param costs 4 FLOPs/token
    (fwd 2 + input-grad 2) instead of 6 — adapter weight-grads are
    O(rank/dim) and ignored. Attention (parameter-free) backward is
    unchanged. Without this, LoRA MFU reads ~1.5× too high."""
    mat = (4.0 if frozen_base else 6.0) * matmul_param_count(cfg)
    # score (QK^T) + weighted value (PV): 2·2·H·hd·T fwd, ×3 with bwd
    attn = 12.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * seq_len
    if causal:
        attn /= 2.0
    return mat + attn


def mfu(tokens_per_sec: float, cfg: LlamaConfig, seq_len: int,
        n_devices: int, peak_flops_per_device: float) -> float:
    """Model FLOPs utilization in [0, 1]."""
    achieved = tokens_per_sec * train_flops_per_token(cfg, seq_len)
    return achieved / (n_devices * peak_flops_per_device)


# ---------------------------------------------------------------------------
# the hybrid family (models.nemotron_h): serving counts that know the
# experts held, the assignments that met them and the recurrent state.
# The benchmark's own copy is perf/flops_nemotron_h.py (a test holds
# the two equal).
# ---------------------------------------------------------------------------


def hybrid_dense_matmul_params(cfg) -> int:
    """Matmul parameters every token of a ``NemotronHConfig`` meets:
    the mixers' projections, the expert layers' router, latent
    projections and shared expert, the head's rows held. Not the
    embedding, gains, the convolution nor the routed experts."""
    Lm, Le, La = (cfg.pattern.count(c) for c in "ME*")
    D, di, cd = cfg.dim, cfg.d_inner, cfg.conv_dim
    mamba = D * (di + cd + cfg.mamba_heads) + di * D
    attn = (2 * D * cfg.n_heads * cfg.head_dim
            + 2 * D * cfg.n_kv_heads * cfg.head_dim)
    experts = (D * cfg.n_routed_experts + 2 * D * cfg.latent_dim
               + 2 * D * cfg.shared_dim)
    return Lm * mamba + La * attn + Le * experts + D * cfg.vocab_size


def hybrid_expert_params(cfg) -> int:
    """One routed expert: up and down in the latent space."""
    return 2 * cfg.latent_dim * cfg.expert_dim


def hybrid_state_flops_per_token(cfg) -> float:
    """A token's recurrence over every Mamba layer: decay and outer
    product into the state (3 a state element), the read-out (2), the
    convolution's taps (2 a tap a channel)."""
    return cfg.pattern.count("M") * (
        5.0 * cfg.d_inner * cfg.state_size
        + 2.0 * cfg.conv_kernel * cfg.conv_dim)


def hybrid_serve_flops(cfg, tokens: float, assignments_held: float,
                       positions_attended: float) -> float:
    """Forward work of ``tokens`` fed tokens of which
    ``assignments_held`` token-expert pairs met an expert held here
    (``engine.device_counters()``) and which attended
    ``positions_attended`` positions in each attention layer."""
    return (2.0 * hybrid_dense_matmul_params(cfg) * tokens
            + 2.0 * hybrid_expert_params(cfg) * assignments_held
            + 4.0 * cfg.pattern.count("*") * cfg.n_heads * cfg.head_dim
            * positions_attended
            + hybrid_state_flops_per_token(cfg) * tokens)


def hybrid_decode_step_bytes(cfg, experts_active: float, live_slots: float,
                             live_kv_tokens: float,
                             bytes_per_value: int = 2) -> float:
    """Bytes one decode step must move once: the matmul weights outside
    the routed experts (the router's in float32), the weights of the
    ``experts_active`` held experts that met a token (summed over the
    expert layers), the live slots' recurrent state read and written
    with their convolution tails, the keys and values attended."""
    Lm, Le, La = (cfg.pattern.count(c) for c in "ME*")
    weights = (bytes_per_value * hybrid_dense_matmul_params(cfg)
               + (4 - bytes_per_value) * Le * cfg.dim * cfg.n_routed_experts
               + bytes_per_value * hybrid_expert_params(cfg) * experts_active)
    state = live_slots * Lm * 2 * (
        4 * cfg.d_inner * cfg.state_size
        + bytes_per_value * (cfg.conv_kernel - 1) * cfg.conv_dim)
    kv = (bytes_per_value * 2.0 * La * cfg.n_kv_heads * cfg.head_dim
          * live_kv_tokens)
    return weights + state + kv
