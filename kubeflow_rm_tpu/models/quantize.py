"""Weight-only int8 / int4 quantization for serving.

Single-sequence decode is weights-bound: every token-step streams the
full parameter set out of HBM while the MXU idles. Cutting the bytes
(bf16 → int8, or → packed int4 + per-group scales) is therefore nearly
a linear token-rate lever, with no activation quantization and no
retraining — the standard weight-only serving recipe, implemented
jax-native.

- **int8** (``bits=8``): symmetric per-output-channel scales,
  ``scale = max|w| / 127`` over the contraction axis, stored fp32.
  Leaves are ``{"q": int8, "s": fp32}``.
- **int4** (``bits=4``): symmetric per-group scales (``group_size``
  rows of the contraction axis share one scale per output channel —
  finer granularity recovers most of the accuracy the 15-level grid
  loses), two nibbles packed per int8 byte. Leaves are
  ``{"q4": int8 packed, "s": fp32}``; a 7B model stores in
  ~3.6 GB — comfortable on one 16 GiB v5e next to its KV cache.
  Leaves carry only stacked arrays (no scalar metadata) so they ride
  ``lax.scan`` over the layer axis like every other weight.
- **int4 decode speed**: the nibble unpack is loop-invariant, so the
  fused decode path hoists it out of the per-token scan
  (``unpack_int4_params`` → ``{"q8g", "s"}`` group-shaped int8 leaves,
  unpacked ONCE per generation) and each step pays only the int8→bf16
  dequant prologue. Early revisions re-unpacked inside the scan body
  every step, which made fused int4 8x+ slower than the per-token
  loop (612.77 vs 137.07 ms/tok at B8/7B, BENCH_SWEEP_r05.json
  ``decode_7b``) and earned the docstring claim that int4 was "a
  capacity lever, not a speed lever". With the hoist that claim is
  stale: the fused scan no longer re-unpacks, while a 7B still stores
  in ~3.6 GB packed + ~6.7 GB unpacked-resident during decode (its
  step cost on the chip: not measured).
- The dequant multiply fuses into the matmul epilogue; XLA reads the
  narrow weights from HBM and converts in VMEM, which is exactly where
  the bandwidth win comes from. Norms (tiny) and the embedding (a
  gather, one row per token) stay in the original dtype.
- ``models.generate.decode_chunk`` consumes quantized and plain
  pytrees interchangeably (``maybe_dequant``), so ``generate`` and the
  sharded ``make_decode_step`` work unchanged.

Accuracy and the speed claim are covered by ``tests/test_quantize.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: weight leaves consumed by matmuls in the decode path
_MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                  "moe_gate", "moe_up", "moe_down")


def _quant_leaf(w: jax.Array) -> dict:
    """Symmetric int8 over the contraction axis (-2 in our (in, out)
    layout; leading axes are layer/expert stacks)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "s": scale}


def _quant_leaf4(w: jax.Array, group_size: int) -> dict:
    """Symmetric int4 with per-(group, out-channel) scales, two values
    packed per byte along the contraction axis."""
    wf = w.astype(jnp.float32)
    K = wf.shape[-2]
    if K % 2:
        raise ValueError(
            f"int4 packing needs an even contraction dim, got {K} "
            "(real transformer dims are even; pad or use int8)")
    g = min(group_size, K)
    if K % g or g % 2:
        g = K  # indivisible or odd group: fall back to one group
    gshape = wf.shape[:-2] + (K // g, g) + wf.shape[-1:]
    wg = wf.reshape(gshape)                      # (..., G, g, out)
    amax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 7.0)
    q = jnp.clip(jnp.round(wg / scale), -7, 7).astype(jnp.int8)
    hi, lo = q[..., 0::2, :], q[..., 1::2, :]    # (..., G, g/2, out)
    packed = ((hi << 4) | (lo & 0xF)).astype(jnp.int8)
    # NOTE: every leaf must carry the leading layer-stack axis so the
    # pytree rides lax.scan's xs unstacking — no scalar metadata here
    # (group size is recoverable as 2 * q4.shape[-2])
    return {"q4": packed, "s": scale}


def _quant_fn(bits: int, group_size: int):
    """The bits→leaf-quantizer dispatch shared by ``quantize_params``
    and ``init_params_quantized``."""
    if bits == 8:
        return _quant_leaf
    if bits == 4:
        return lambda w: _quant_leaf4(w, group_size)
    raise ValueError(f"bits must be 8 or 4, got {bits}")


def quantize_params(params: dict, bits: int = 8,
                    group_size: int = 128) -> dict:
    """Quantize every matmul weight to ``bits`` (8 or 4);
    norms/embed pass through. ``group_size`` applies to int4 only."""
    quant = _quant_fn(bits, group_size)
    blocks = {
        k: (quant(v) if k in _MATMUL_LEAVES else v)
        for k, v in params["blocks"].items()
    }
    out = dict(params, blocks=blocks)
    out["lm_head"] = quant(params["lm_head"])
    return out


@partial(jax.jit, static_argnames=("cfg", "name", "shape", "bits",
                                   "group_size"))
def _init_quant_leaf(key: jax.Array, cfg, name: str, shape: tuple,
                     bits: int, group_size: int):
    """Init one matmul leaf and quantize it inside a single jitted
    call, so the full-precision tensor is a transient. Module-level
    on purpose: the trace cache keys on the static (name, shape) —
    one compile per distinct leaf spec across ALL calls, where the
    old per-leaf ``jax.jit(lambda ...)`` built a fresh single-entry
    cache every iteration (KFRM007)."""
    from kubeflow_rm_tpu.models.llama import init_leaf

    return _quant_fn(bits, group_size)(init_leaf(cfg, name, shape, key))


def init_params_quantized(cfg, key: jax.Array, bits: int = 8,
                          group_size: int = 128) -> dict:
    """Random-init a model DIRECTLY into quantized form, one leaf at a
    time, so the full-precision copy never exists in HBM.

    ``quantize_params(init_params(cfg, key))`` needs the whole fp32/bf16
    tree resident before the first leaf quantizes — for a 7B that is
    ~13-27 GiB and OOMs a 16 GiB v5e. Here each matmul leaf runs
    init→quantize inside ONE jitted call whose full-precision tensor is
    a transient (largest: the stacked w_up, ~2.9 GiB bf16 at 7B), so
    peak HBM is the quantized model plus one leaf. Bit-identical to the
    two-step path (asserted by tests/test_quantize.py) because it
    splits keys and applies the same init/quant math in the same order.

    This is the synthetic-weights entry the 7B serving/QLoRA benches
    use; ``from_hf_llama`` + ``quantize_params`` on a big-RAM host is
    the real-checkpoint equivalent.
    """
    from kubeflow_rm_tpu.models.llama import init_leaf, param_spec_shapes

    # dispatch shapes like models.init_params does (MixtralConfig
    # reuses llama's init rules over its own shape tree)
    from kubeflow_rm_tpu.models.mixtral import MixtralConfig
    from kubeflow_rm_tpu.models.mixtral import (
        param_spec_shapes as moe_shapes,
    )
    shapes = (moe_shapes(cfg) if isinstance(cfg, MixtralConfig)
              else param_spec_shapes(cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(flat))

    leaves = []
    for (path, shape), k in zip(flat, keys):
        name = path[-1].key
        if name in _MATMUL_LEAVES or name == "lm_head":
            leaves.append(jax.block_until_ready(
                _init_quant_leaf(k, cfg, name, tuple(shape),
                                 bits, group_size)))
        else:
            leaves.append(init_leaf(cfg, name, shape, k))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) in ({"q", "s"},
                                                    {"q4", "s"},
                                                    {"q8g", "s"})


def unpack_int4(leaf: dict) -> dict:
    """Unpack a packed-int4 leaf to group-shaped int8 ``{"q8g", "s"}``.

    The unpack here is byte-for-byte the ops the old in-scan q4 dequant
    performed, so ``maybe_dequant`` on the result is bit-identical to
    dequanting the packed form directly — the fused decode loop relies
    on that for loop/fused parity. Unlike plain int8 ``{"q", "s"}``,
    the group axes are kept so the per-group scales still broadcast.
    Doubles the weight bytes vs packed (int8 vs two nibbles/byte);
    intended as a transient inside a generation, not a storage format.
    """
    packed = leaf["q4"]                          # (..., G, g/2, out)
    hi = packed >> 4                             # arithmetic: sign ok
    lo = (packed << 4).astype(jnp.int8) >> 4
    q = jnp.stack([hi, lo], axis=-2)             # (..., G, g/2, 2, out)
    gshape = packed.shape[:-2] + (packed.shape[-2] * 2,) \
        + packed.shape[-1:]
    return {"q8g": q.reshape(gshape), "s": leaf["s"]}


def _is_quant_leaf(x) -> bool:
    """A quantized weight: a dict the tree walks must not descend."""
    return isinstance(x, dict) and ("q4" in x or "q8g" in x or "q" in x)


def unpack_int4_params(params):
    """Rewrite every packed-int4 leaf in a param tree to its unpacked
    ``{"q8g", "s"}`` form; every other leaf passes through untouched.

    Called ONCE at the top of the fused decode paths (outside the
    per-token scan) so nibble unpacking is loop-invariant — the fix
    for the 612.77 ms/tok fused-int4 trap. No-op on int8/bf16 trees.
    """
    return jax.tree_util.tree_map(
        lambda x: unpack_int4(x) if isinstance(x, dict) and "q4" in x
        else x,
        params, is_leaf=_is_quant_leaf)


def has_int4_leaf(params) -> bool:
    """Does the tree hold a packed-int4 leaf for ``unpack_int4_params``
    to rewrite?"""
    return any(isinstance(x, dict) and "q4" in x for x in
               jax.tree_util.tree_leaves(params, is_leaf=_is_quant_leaf))


def maybe_dequant(leaf, dtype) -> jax.Array:
    """Materialize a compute-dtype weight from any representation.
    Under jit the unpack/convert/scale fuses into the consuming
    matmul's prologue."""
    if not isinstance(leaf, dict):
        return leaf.astype(dtype)
    if "q4" in leaf:
        leaf = unpack_int4(leaf)
    if "q8g" in leaf:
        q = leaf["q8g"]                          # (..., G, g, out)
        w = q.astype(dtype) * leaf["s"].astype(dtype)
        K = q.shape[-3] * q.shape[-2]
        return w.reshape(q.shape[:-3] + (K,) + q.shape[-1:])
    return (leaf["q"].astype(dtype) * leaf["s"].astype(dtype))


def quantized_bytes(params: dict) -> int:
    """Total stored bytes — the HBM-traffic accounting behind the
    decode speedup claim."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(params))
