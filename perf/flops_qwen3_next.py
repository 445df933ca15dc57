"""Operations and bytes the ``qwen3_next`` family's serving work needs,
from its shapes: what ``perf/flops_nemotron_h.py`` is to the
``nemotron_h`` family. Kept with the benchmark so that no PR that
claims a gain can change the yardstick; the program's own copy is
``utils/flops.py`` (a test holds the two equal). ``d`` is
``reference_qwen3_next.dims_of(config)``.

Counts know what a token really touches: the experts **held** here and
the token-expert assignments routed to them (a counter of the
program's, not ``top_k``: half of a token's choices belong to the
other chip), the delta rule's own arithmetic, and the one attention
layer for positions attended. Each is the least the work needs: a
reading over 100 % of a peak means a count here is too high.
"""


def _kinds(d: dict) -> tuple[int, int, int]:
    return tuple(d["pattern"].count(c) for c in "LFE")


def dense_matmul_params(d: dict) -> int:
    """Matmul parameters every token meets: the mixers' projections,
    the expert layers' router, shared expert and its gate, and the
    head's rows held. Not the embedding (a gather), gains, the
    convolution (counted with the state) nor the routed experts."""
    Ll, Lf, Le = _kinds(d)
    D = d["D"]
    key_dim, value_dim = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
    linear = (D * (2 * key_dim + 2 * value_dim) + D * 2 * d["Hv"]
              + value_dim * D)
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    full = D * 2 * H * hd + 2 * D * KVH * hd + H * hd * D
    experts = D * d["router"] + 3 * D * d["Fs"] + D
    return Ll * linear + Lf * full + Le * experts + D * d["V"]


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["D"] * d["F"]


def state_flops_per_token(d: dict) -> float:
    """A token's delta rule over every linear layer: the decay (1 a
    state element), ``S^T k`` (2), the rank-one update (2) and the
    read-out ``S^T q`` (2); and the convolution's taps (2 a tap a
    channel)."""
    Ll = _kinds(d)[0]
    conv_dim = 2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"]
    return Ll * (7.0 * d["Hv"] * d["dk"] * d["dv"]
                 + 2.0 * d["K"] * conv_dim)


def serve_flops(d: dict, tokens: float, assignments_held: float,
                positions_attended: float) -> float:
    """Forward work of ``tokens`` fed tokens (prefill's and decode's)
    of which ``assignments_held`` token-expert pairs met an expert held
    here and which attended ``positions_attended`` positions in each
    attention layer: 2 FLOPs a matmul parameter met, 4 x H x hd a
    position attended (scores and values), the delta rule's own."""
    Lf = _kinds(d)[1]
    return (2.0 * dense_matmul_params(d) * tokens
            + 2.0 * expert_params(d) * assignments_held
            + 4.0 * Lf * d["H"] * d["hd"] * positions_attended
            + state_flops_per_token(d) * tokens)


def deltanet_decode_bytes(d: dict, live_slots: float) -> float:
    """Bytes a decode step's ``gated_delta_decode`` calls must move, one
    a linear layer: for each live slot its (Hv, dk, dv) float32 state
    read and written, its q and k (Hv, dk), v (Hv, dv) and the gates'
    row (Hv, 128) read and its output (Hv, dv) written, in float32."""
    Ll = _kinds(d)[0]
    Hv, dk, dv = d["Hv"], d["dk"], d["dv"]
    a_slot = 4 * (2 * Hv * dk * dv + 2 * Hv * dk + 2 * Hv * dv + Hv * 128)
    return Ll * live_slots * a_slot


def expert_bytes(d: dict, bytes_per_value: int = 2) -> int:
    """One routed expert's three matrices."""
    return bytes_per_value * expert_params(d)
