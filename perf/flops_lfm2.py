"""Operations and bytes the ``lfm2_moe`` family's serving work needs,
from its shapes: what ``perf/flops_afmoe.py`` is to the ``afmoe``
family. Kept with the benchmark so that no PR that claims a gain can
change the yardstick. ``d`` is ``reference_lfm2.dims_of(config)``.

Counts know what a token really touches: the short convolution's
taps, each attention layer's keys (the program's counter of the pairs
one attention layer attended), the dense layers, and the token-expert
assignments routed to the experts held here (a counter of the
program's). Each is the least the work needs: a reading over 100 % of
a peak means a count here is too high.
"""


def kinds(d: dict) -> dict:
    """Parts of each kind: ``C`` conv, ``A`` attention, ``D`` dense,
    ``E`` experts."""
    return {c: d["pattern"].count(c) for c in "CADE"}


def dense_matmul_params(d: dict) -> int:
    """Matmul parameters every token meets: the conv layers' in and out
    projections; the attention's q, k, v and output; the dense layers'
    three matrices; the expert layers' router; the head (the
    embedding's rows, tied). Not the embedding's gather, gains, the
    convolution's taps, nor the routed experts."""
    n = kinds(d)
    D, H, KVH, hd = d["D"], d["H"], d["KVH"], d["hd"]
    conv = 3 * D * D + D * D
    attention = 2 * D * H * hd + 2 * D * KVH * hd
    return (n["C"] * conv + n["A"] * attention + n["D"] * 3 * D * d["Fd"]
            + n["E"] * D * d["E"] + D * d["V"])


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["D"] * d["F"]


def conv_flops_per_token(d: dict) -> int:
    """The short convolution's taps, a token a conv layer: a multiply
    and an add a tap and channel (the gates' products are not
    counted)."""
    return 2 * d["K"] * d["D"]


def fed(spans: list[dict]) -> dict:
    """The tokens the answer tokens of ``spans`` (``stamps.in_window``'s)
    fed through the model and the columns the head must be applied to:
    a decode token ``j >= 1`` comes out of a step that fed one column
    and applied the head to it; a span whose first token is inside adds
    its whole prompt's prefill (no prefix is adopted), whose head is
    needed at the last real column alone."""
    tokens = heads = 0
    for s in spans:
        steps = max(0, s["hi"] - max(s["lo"], 1))
        tokens += steps
        heads += steps
        if s["prefill"]:
            tokens += s["prompt_len"]
            heads += 1
    return {"tokens": tokens, "heads": heads}


def serve_flops(d: dict, tokens: float, heads: float,
                assignments_held: float, pairs: float) -> float:
    """Forward work of ``tokens`` fed tokens, ``heads`` of them put
    through the head, of which ``assignments_held`` token-expert pairs
    met an expert held here, and whose queries attended ``pairs`` keys
    in each attention layer: 2 FLOPs a matmul parameter met, 4 x H x
    hd a key attended (scores and values), the convolution's taps."""
    n = kinds(d)
    head = d["D"] * d["V"]
    return (2.0 * (dense_matmul_params(d) - head) * tokens
            + 2.0 * head * heads
            + 2.0 * expert_params(d) * assignments_held
            + 4.0 * d["H"] * d["hd"] * n["A"] * pairs
            + conv_flops_per_token(d) * n["C"] * tokens)


def weight_bytes(d: dict, bytes_per_value: int = 2) -> float:
    """Every weight outside the routed experts, read once: the matrices
    of ``dense_matmul_params`` (the router's in float32), the
    convolution's taps and the gains."""
    n = kinds(d)
    D = d["D"]
    gains = (2 * (n["C"] + n["A"]) + 1) * D + 2 * n["A"] * d["hd"]
    return (bytes_per_value * (dense_matmul_params(d)
                               + n["C"] * d["K"] * D + gains)
            + (4 - bytes_per_value) * n["E"] * D * d["E"]
            + 4 * n["E"] * d["E"])


def expert_bytes(d: dict, bytes_per_value: int = 2) -> int:
    """One routed expert's three matrices (22.02 MB at the published
    widths in bfloat16)."""
    return bytes_per_value * expert_params(d)


def kv_bytes_per_position(d: dict, bytes_per_value: int = 2) -> int:
    """A position's key and value in one attention layer (2 KB at the
    published widths)."""
    return 2 * d["KVH"] * d["hd"] * bytes_per_value


def decode_step_bytes(d: dict, experts_active: float, live_slots: float,
                      pairs: float, bytes_per_value: int = 2) -> float:
    """Bytes one decode step must move once: every weight outside the
    routed experts; the ``experts_active`` experts that met a token
    (summed over the expert layers; an expert nobody chose need not be
    read); the live slots' convolution tails, read and written; and the
    keys and values of the ``pairs`` positions the live slots attended
    in each attention layer."""
    n = kinds(d)
    tails = live_slots * n["C"] * 2 * (d["K"] - 1) * d["D"] * bytes_per_value
    return (weight_bytes(d, bytes_per_value)
            + expert_bytes(d, bytes_per_value) * experts_active + tails
            + kv_bytes_per_position(d, bytes_per_value) * n["A"] * pairs)
