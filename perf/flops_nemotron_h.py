"""Operations and bytes the ``nemotron_h`` family's serving work needs,
from its shapes: what ``perf/flops.py`` is to the dense decoder. Kept
with the benchmark so that no PR that claims a gain can change the
yardstick; the program's own copy is ``utils/flops.py`` (a test holds
the two equal). ``d`` is ``reference_nemotron_h.dims_of(config)``.

Counts know what a token really touches: the experts **held** here
and the token-expert assignments routed to them (a counter of the
program's, not ``top_k``: three quarters of a token's choices belong
to other chips), the recurrent state's own arithmetic, and the
attention layers alone for positions attended. Each is the least the
work needs: a reading over 100 % of a peak means a count here is too
high.
"""


def _kinds(d: dict) -> tuple[int, int, int]:
    return tuple(d["pattern"].count(c) for c in "ME*")


def dense_matmul_params(d: dict) -> int:
    """Matmul parameters every token meets: the mixers' projections,
    the experts layers' router, latent projections and shared expert,
    and the head's rows held. Not the embedding (a gather), gains, the
    convolution (counted with the state) nor the routed experts."""
    Lm, Le, La = _kinds(d)
    D, di = d["D"], d["Hm"] * d["P"]
    cd = di + 2 * d["G"] * d["N"]
    mamba = D * (di + cd + d["Hm"]) + di * D
    attn = 2 * D * d["H"] * d["hd"] + 2 * D * d["KVH"] * d["hd"]
    experts = (D * d["router"] + 2 * D * d["latent"] + 2 * D * d["Fs"])
    return Lm * mamba + La * attn + Le * experts + D * d["V"]


def expert_params(d: dict) -> int:
    """One routed expert: up and down in the latent space."""
    return 2 * d["latent"] * d["F"]


def state_flops_per_token(d: dict) -> float:
    """A token's recurrence over every Mamba layer: the decay and the
    outer product into the state (3 a state element), the read-out (2)
    and the convolution's taps (2 a tap a channel)."""
    Lm = _kinds(d)[0]
    di = d["Hm"] * d["P"]
    cd = di + 2 * d["G"] * d["N"]
    return Lm * (5.0 * di * d["N"] + 2.0 * d["K"] * cd)


def serve_flops(d: dict, tokens: float, assignments_held: float,
                positions_attended: float) -> float:
    """Forward work of ``tokens`` fed tokens (prefill's and decode's)
    of which ``assignments_held`` token-expert pairs met an expert held
    here and which attended ``positions_attended`` positions in each
    attention layer: 2 FLOPs a matmul parameter met, 4 x H x hd a
    position attended (scores and values), the state's own."""
    La = _kinds(d)[2]
    return (2.0 * dense_matmul_params(d) * tokens
            + 2.0 * expert_params(d) * assignments_held
            + 4.0 * La * d["H"] * d["hd"] * positions_attended
            + state_flops_per_token(d) * tokens)


def decode_step_bytes(d: dict, experts_active: float, live_slots: float,
                      live_kv_tokens: float,
                      bytes_per_value: int = 2) -> float:
    """Bytes one decode step must move once: every matmul weight
    outside the routed experts (the router's in float32), the weights
    of the ``experts_active`` held experts that met a token (summed
    over the expert layers; an expert nobody chose need not be read),
    the live slots' recurrent state read and written (float32) with
    their convolution tails, and the keys and values of the positions
    the live slots attend to."""
    Lm, Le, La = _kinds(d)
    di = d["Hm"] * d["P"]
    cd = di + 2 * d["G"] * d["N"]
    weights = (bytes_per_value * dense_matmul_params(d)
               + (4 - bytes_per_value) * Le * d["D"] * d["router"]
               + bytes_per_value * expert_params(d) * experts_active)
    state = live_slots * Lm * 2 * (4 * di * d["N"]
                                   + bytes_per_value * (d["K"] - 1) * cd)
    kv = bytes_per_value * 2.0 * La * d["KVH"] * d["hd"] * live_kv_tokens
    return weights + state + kv
