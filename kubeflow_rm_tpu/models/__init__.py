"""Model zoo. ``init_params`` / ``forward_with_aux`` dispatch on the
config type so generic code (training, bench, dryrun) never branches on
model families itself."""

import jax

from kubeflow_rm_tpu.models import llama as _llama
from kubeflow_rm_tpu.models import mixtral as _mixtral
from kubeflow_rm_tpu.models import nemotron_h as _nemotron_h
from kubeflow_rm_tpu.models.convert import config_from_hf, from_hf_llama
from kubeflow_rm_tpu.models.lora import add_lora, lora_mask, merge_lora
from kubeflow_rm_tpu.models.quantize import (
    maybe_dequant,
    quantize_params,
    unpack_int4_params,
)
from kubeflow_rm_tpu.models.generate import (
    ContinuousBatchingEngine,
    EngineRequest,
    KVCache,
    cache_shardings,
    decode_chunk,
    generate,
    generate_fused,
    generate_speculative_fused,
    init_cache,
    make_decode_step,
    make_generate_step,
)
from kubeflow_rm_tpu.models.generate import (
    DEFAULT_CLASS_WEIGHTS,
    SLO_CLASSES,
)
from kubeflow_rm_tpu.models.llama import LlamaConfig, forward
from kubeflow_rm_tpu.models.mixtral import MixtralConfig
from kubeflow_rm_tpu.models.nemotron_h import NemotronHConfig


def init_params(cfg: LlamaConfig, key: jax.Array) -> dict:
    """Family-correct parameter init for any model config."""
    if isinstance(cfg, MixtralConfig):
        return _mixtral.init_params(cfg, key)
    if isinstance(cfg, NemotronHConfig):
        return _nemotron_h.init_params(cfg, key)
    return _llama.init_params(cfg, key)


def forward_with_aux(params, tokens, cfg: LlamaConfig, **kwargs):
    """Uniform forward: returns (logits, aux) where aux is the router
    load-balancing loss for MoE families and None for dense ones.
    ``mesh`` (kwarg) enables explicit sequence-parallel attention
    schedules when ``cfg.attention_backend`` asks for one."""
    if isinstance(cfg, MixtralConfig):
        return _mixtral.forward(params, tokens, cfg, **kwargs)
    if isinstance(cfg, NemotronHConfig):
        return _nemotron_h.forward(params, tokens, cfg, **kwargs), None
    return _llama.forward(params, tokens, cfg, **kwargs), None


from kubeflow_rm_tpu.models.paging import (
    BlockPool,
    PagedKVCache,
    init_paged_cache,
    paged_decode_step,
    paged_prefill,
    prefix_keys,
)

__all__ = ["BlockPool", "ContinuousBatchingEngine",
           "DEFAULT_CLASS_WEIGHTS", "EngineRequest", "KVCache",
           "PagedKVCache", "SLO_CLASSES",
           "init_paged_cache", "paged_decode_step", "paged_prefill",
           "prefix_keys",
           "LlamaConfig", "MixtralConfig", "NemotronHConfig", "add_lora",
           "config_from_hf",
           "cache_shardings", "decode_chunk", "forward", "forward_with_aux", "from_hf_llama",
           "generate", "generate_fused", "generate_speculative_fused",
           "init_cache", "init_params",
           "make_decode_step", "make_generate_step",
           "lora_mask", "maybe_dequant", "merge_lora", "quantize_params",
           "unpack_int4_params"]
