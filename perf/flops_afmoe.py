"""Operations and bytes the ``afmoe`` family's serving work needs, from
its shapes: what ``perf/flops_qwen3_next.py`` is to the ``qwen3_next``
family. Kept with the benchmark so that no PR that claims a gain can
change the yardstick. ``d`` is ``reference_afmoe.dims_of(config)``.

Counts know what a token really touches: each layer's reach (a window
layer attends ``min(context, window)`` keys, a full layer all of
them), the dense layers, and the experts **held** here with the
token-expert assignments routed to them (a counter of the program's,
not ``top_k``: seven of a token's eight choices belong to other chips
on average). Each is the least the work needs: a reading over 100 % of
a peak means a count here is too high.
"""

from perf import flops


def kinds(d: dict) -> dict:
    """Layers of each part: ``W`` window, ``F`` full, ``D`` dense,
    ``E`` experts."""
    return {c: d["pattern"].count(c) for c in "WFDE"}


def dense_matmul_params(d: dict) -> int:
    """Matmul parameters every token meets: the attention's q, k, v,
    output gate and output; the dense layers' three matrices; the
    expert layers' router and shared expert; the head's rows held. Not
    the embedding (a gather), gains, nor the routed experts."""
    n = kinds(d)
    D, H, KVH, hd = d["D"], d["H"], d["KVH"], d["hd"]
    attention = 3 * D * H * hd + 2 * D * KVH * hd
    dense = 3 * D * d["Fd"]
    experts = D * d["router"] + 3 * D * d["Fs"]
    return ((n["W"] + n["F"]) * attention + n["D"] * dense
            + n["E"] * experts + D * d["V"])


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["D"] * d["F"]


def window_pairs(n: int, window: int) -> int:
    """(query, key) pairs a causal window of ``window`` holds over the
    first ``n`` positions: position ``p`` attends ``min(p + 1, window)``."""
    inside = min(n, window)
    return inside * (inside + 1) // 2 + (n - inside) * window


def attended(spans: list[dict], window: int) -> dict:
    """What the answer tokens of ``spans`` (``stamps.in_window``'s) fed
    and attended, a layer of each kind: a decode token ``j >= 1`` comes
    out of a step at context ``prompt + j``, itself among the keys; a
    span whose first token is inside adds its whole prompt's prefill
    (no prefix is adopted). ``heads`` counts the columns the head is
    applied to: a decode step's one a token, a prefill's last alone."""
    tokens = heads = full = win = 0
    for s in spans:
        n = s["prompt_len"]
        for j in range(max(s["lo"], 1), s["hi"]):
            tokens += 1
            heads += 1
            full += n + j
            win += min(n + j, window)
        if s["prefill"]:
            tokens += n
            heads += 1
            full += n * (n + 1) // 2
            win += window_pairs(n, window)
    return {"tokens": tokens, "heads": heads, "full_positions": full,
            "window_positions": win}


def serve_flops(d: dict, tokens: float, heads: float,
                assignments_held: float, full_positions: float,
                window_positions: float) -> float:
    """Forward work of ``tokens`` fed tokens, ``heads`` of them put
    through the head, of which ``assignments_held`` token-expert pairs
    met an expert held here and which attended ``full_positions`` keys
    in each full layer and ``window_positions`` in each window layer: 2
    FLOPs a matmul parameter met, 4 x H x hd a key attended (scores and
    values)."""
    n = kinds(d)
    head = d["D"] * d["V"]
    return (2.0 * (dense_matmul_params(d) - head) * tokens
            + 2.0 * head * heads
            + 2.0 * expert_params(d) * assignments_held
            + 4.0 * d["H"] * d["hd"] * (n["F"] * full_positions
                                        + n["W"] * window_positions))


def decode_attention_bytes(d: dict, full_positions: float,
                           window_positions: float,
                           bytes_per_value: int = 2) -> float:
    """Bytes a decode step's attention kernels must read: each key and
    value attended once, ``full_positions`` in each full layer and
    ``window_positions`` in each window layer (2 KB a position a layer
    at the published widths)."""
    n = kinds(d)
    a_position = 2 * d["KVH"] * d["hd"] * bytes_per_value
    return a_position * (n["F"] * full_positions
                         + n["W"] * window_positions)


def prefill_attention_cost(d: dict, n: int, windowed: bool
                           ) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill attention call over a prompt of
    ``n`` real columns, causal or windowed: ``flops.flash_call_cost`` at
    the pairs the mask leaves among them. The bucket's padding columns
    are no work the prompt needs, so they are not counted."""
    pairs = window_pairs(n, d["window"]) if windowed else n * (n + 1) // 2
    return flops.flash_call_cost(d, 1, n, pairs, backward=False)
