"""Multi-head attention for training.

The default implementation is plain XLA: one batched matmul for scores,
an fp32 softmax, one batched matmul for the output. On TPU this maps
directly onto the MXU and, combined with per-layer rematerialization in
the model (see ``models/llama.py``), keeps only one layer's (B, H, T, T)
score tensor live at a time — at fine-tuning sequence lengths (<= 8k)
that is both faster to compile and competitive with a hand-written
kernel. A pallas flash-attention path can be slotted in through the same
signature for long-context runs; ring attention for sequence-parallel
long context lives in ``parallel/ring_attention.py`` and reuses the same
blockwise math.

GQA (n_kv_heads < n_heads) is expressed by reshaping queries into
(kv_head, group) rather than materializing repeated K/V — the einsum
contracts over the shared kv head axis so K/V stay at their true size in
HBM.
"""

import contextlib
import contextvars
import logging

import jax
import jax.numpy as jnp

log = logging.getLogger(__name__)

# the lists ``kernel_choices`` has open in this context (thread, task)
_recording: contextvars.ContextVar[tuple[list, ...]] = \
    contextvars.ContextVar("kernel_choices", default=())

NEG_INF = -2.0**30  # large-but-finite: keeps fp32 softmax NaN-free on fully masked rows


def computation_devices(x: jax.Array, mesh=None) -> tuple[str, int]:
    """(platform, device count) of the computation ``x`` belongs to.

    The caller's ``mesh`` decides when there is one. Otherwise the
    operand's own type does: an array laid out over a mesh — concrete
    or a tracer under ``jit`` — carries that mesh, a one-device array
    carries none. A one-device tracer names no device at all, so its
    platform is the default backend, which is where ``jit`` places an
    uncommitted computation. Never ``jax.device_count()``: a host that
    shows four chips still runs one-device programs.
    """
    if mesh is not None:
        return mesh.devices.flat[0].platform, mesh.devices.size
    laid_out = jax.typeof(x).sharding.mesh
    n = 1 if laid_out.empty else laid_out.size
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform, n
    return jax.default_backend(), n


def _log_choice(kernel: str, interpret: bool, platform: str,
                n_devices: int, why: str) -> None:
    """Say which kernel a flash-eligible call got. Under ``jit`` this
    runs while tracing, so a compiled program says it once."""
    log.info("attention kernel=%s interpret=%s platform=%s devices=%d "
             "(%s)", kernel, interpret, platform, n_devices, why)
    for chosen in _recording.get():
        chosen.append(kernel)


@contextlib.contextmanager
def kernel_choices():
    """The kernels (``_log_choice``'s names: "flash", "xla", ...) that
    the attention calls traced inside the block were given, in order.
    For a caller that has to know what its forward pass runs on — the
    choice is made layers below it, under ``scan`` and ``checkpoint``,
    where nothing can be handed back up."""
    chosen: list[str] = []
    token = _recording.set((*_recording.get(), chosen))
    try:
        yield chosen
    finally:
        _recording.reset(token)


def attention_mask(
    Tq: int,
    Tk: int,
    *,
    causal: bool = True,
    positions_q: jax.Array | None = None,
    positions_kv: jax.Array | None = None,
    segment_ids_q: jax.Array | None = None,
    segment_ids_kv: jax.Array | None = None,
    window: int | None = None,
) -> jax.Array | None:
    """Boolean keep-mask, (Tq, Tk) or (B, Tq, Tk), or None if unmasked.

    Causality uses global positions when given (sequence-parallel shards,
    packed sequences); segment ids — when given — additionally restrict
    attention to ``seg_q == seg_kv`` so packed documents stay independent
    and padding (its own segment) is never attended. ``window`` (causal
    only) keeps the ``window`` newest keys a query sees, itself among
    them: query ``p`` reads keys ``p - window + 1 .. p``.
    """
    mask = None
    if causal:
        if positions_q is None:
            mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]  # (Tq, Tk)
            if window is not None:
                mask &= (jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :]
                         < window)
        else:
            mask = positions_q[:, :, None] >= positions_kv[:, None, :]  # (B, Tq, Tk)
            if window is not None:
                mask &= (positions_q[:, :, None] - positions_kv[:, None, :]
                         < window)
    if segment_ids_q is not None:
        seg = segment_ids_q[:, :, None] == segment_ids_kv[:, None, :]  # (B, Tq, Tk)
        mask = seg if mask is None else mask & seg
    return mask


def flash_eligible(q, k, *, causal, positions_q, bias,
                   segment_ids_q=None) -> bool:
    """Can the pallas flash kernel handle this call exactly?

    Requires: causal self-attention over local indices (no explicit
    positions — packed sequences are covered because local-causal ∧
    same-segment ≡ position-causal ∧ same-segment, see
    ``flash_attention`` docstring), no additive bias, and shapes that
    tile the block sizes the kernel will actually pick (another tile
    for a call with segment ids than for one without).
    """
    from kubeflow_rm_tpu.ops.flash_attention import tile_for
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    return (causal and bias is None and positions_q is None
            and Tq == Tk and all(tile_for(Tq, segment_ids_q is not None))
            and D % 8 == 0)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    positions_q: jax.Array | None = None,
    positions_kv: jax.Array | None = None,
    segment_ids_q: jax.Array | None = None,
    segment_ids_kv: jax.Array | None = None,
    bias: jax.Array | None = None,
    window: int | None = None,
    impl: str = "auto",
    mesh=None,
) -> jax.Array:
    """Scaled dot-product attention.

    Args:
      q: (B, Tq, H, D) queries.
      k, v: (B, Tk, KVH, D) keys/values; H must be a multiple of KVH.
      causal: apply a causal mask. When ``positions_q``/``positions_kv``
        are given (sequence-parallel shards, packed sequences) the mask is
        ``pos_q >= pos_kv``; otherwise it is the standard lower-triangular
        mask over local indices.
      segment_ids_q / segment_ids_kv: optional (B, T) int segment ids for
        packed sequences; attention is restricted to equal segments.
      bias: optional additive bias broadcastable to (B, H, Tq, Tk).
      window: causal only, the keys a query reads: its own and the
        ``window - 1`` before it (a sliding-window layer). The flash
        kernel takes it without segment ids, forward only.

      impl: "auto" (the pallas flash kernel when the computation is a
        one-device TPU program and the call is exactly representable,
        else XLA), "flash" (force the pallas kernel; interpreter
        off-TPU), or "xla" (always the materialized-scores path).
      mesh: the mesh the enclosing computation is sharded over, when
        the caller has one; "auto" reads the target devices from it
        (see ``computation_devices``).

    Returns:
      (B, Tq, H, D) in q.dtype.
    """
    if impl not in ("auto", "flash", "xla"):
        raise ValueError(f"impl must be auto|flash|xla, got {impl!r}")
    if impl == "flash" and (bias is not None or positions_q is not None):
        raise ValueError(
            "impl='flash' cannot represent an additive bias or explicit "
            "positions; use impl='xla' (packed sequences need only "
            "segment ids — see ops/flash_attention.py)")
    platform, n_devices = computation_devices(q, mesh)
    use_flash = impl == "flash"
    if impl == "auto" and flash_eligible(
            q, k, causal=causal, positions_q=positions_q, bias=bias,
            segment_ids_q=segment_ids_q):
        # one device only: pallas_call has no GSPMD partitioning rule,
        # so under a multi-chip jit the compiler would all-gather the
        # FULL global q/k/v onto every device — silently defeating
        # dp/fsdp/sp sharding. Multi-chip meshes keep the einsum path
        # (partitions cleanly) or use the ring schedules;
        # shard_map-wrapping the kernel is the follow-up that lifts
        # this gate.
        use_flash = platform == "tpu" and n_devices == 1
        if not use_flash:
            _log_choice("xla", False, platform, n_devices,
                        "flash-eligible, but not a one-device tpu "
                        "program")
    if use_flash:
        from kubeflow_rm_tpu.ops.flash_attention import flash_attention
        interpret = platform != "tpu"
        _log_choice("flash", interpret, platform, n_devices, f"impl={impl}")
        return flash_attention(
            q, k, v, causal=causal,
            segment_ids_q=segment_ids_q, segment_ids_kv=segment_ids_kv,
            window=window, interpret=interpret)

    B, Tq, H, D = q.shape
    _, Tk, KVH, _ = k.shape
    assert H % KVH == 0, f"n_heads {H} not divisible by n_kv_heads {KVH}"
    G = H // KVH

    scale = D ** -0.5
    qf = (q * scale).reshape(B, Tq, KVH, G, D)

    # scores: (B, KVH, G, Tq, Tk)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, k, preferred_element_type=jnp.float32)

    if bias is not None:
        bias = jnp.broadcast_to(bias, (B, H, Tq, Tk))
        scores = scores + bias.reshape(B, KVH, G, Tq, Tk).astype(jnp.float32)

    mask = attention_mask(
        Tq, Tk, causal=causal,
        positions_q=positions_q, positions_kv=positions_kv,
        segment_ids_q=segment_ids_q, segment_ids_kv=segment_ids_kv,
        window=window,
    )
    if mask is not None:
        if mask.ndim == 2:
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        else:
            scores = jnp.where(mask[:, None, None], scores, NEG_INF)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    return out.reshape(B, Tq, H, D)
