"""Operations and bytes that the work needs, from its shapes, and the
table of peaks. Kept with the benchmark so that no PR that claims a
gain can change the yardstick. The matmul arithmetic is that of the
program's ``utils/flops.py`` (6 FLOPs a matmul parameter a trained
token, 2 a served one, attention added, recomputation not counted);
``d`` is ``reference.dims_of(config)``.
"""

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip, by the exact
    ``device_kind`` jax reports. A kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device_kind "
                       f"{device_kind!r}; add it to {PEAKS_FILE.name} "
                       f"with its source")
    return table[device_kind]


def matmul_params(d: dict) -> int:
    """Parameters that take part in matmuls: the seven matrices of
    each layer and the head; not the embedding (a gather) nor gains."""
    per_layer = (d["D"] * d["H"] * d["hd"] + 2 * d["D"] * d["KVH"] * d["hd"]
                 + d["H"] * d["hd"] * d["D"] + 3 * d["D"] * d["F"])
    return d["L"] * per_layer + d["D"] * d["V"]


def train_flops_per_token(d: dict, seq_len: int) -> float:
    """Forward and backward of one trained token in a row of
    ``seq_len``: 6 a matmul parameter, and the two attention matmuls,
    2 x 2 x H x hd x T forward, three times that with the backward,
    halved because causal. A packed row attends less than this counts:
    the count is the unpacked row's, as the program's own is."""
    attn = 12.0 * d["L"] * d["H"] * d["hd"] * seq_len / 2.0
    return 6.0 * matmul_params(d) + attn


def serve_flops(d: dict, prompt: int, new: int, prefilled: int) -> float:
    """One request's forward work: ``prefilled`` of its ``prompt``
    tokens through prefill and ``new`` - 1 through decode steps (the
    first answer token is picked from prefill's logits), each 2 a matmul
    parameter; attention 4 x L x H x hd a token a position attended
    (scores and values, 2 FLOPs a multiply-add), every token attending
    all before it and itself."""
    tokens = prefilled + new - 1        # the last one picked is not fed
    first = prompt - prefilled          # context the first one sees
    attended = tokens * first + tokens * (tokens + 1) / 2.0
    return (2.0 * matmul_params(d) * tokens
            + 4.0 * d["L"] * d["H"] * d["hd"] * attended)


def decode_step_bytes(d: dict, live_kv_tokens: float,
                      bytes_per_value: int = 2) -> float:
    """Bytes one decode step must read once: every matmul weight, and
    the keys and values of the positions the live slots attend to."""
    kv = 2.0 * d["L"] * d["KVH"] * d["hd"] * live_kv_tokens
    return bytes_per_value * (matmul_params(d) + kv)


def flash_call_cost(d: dict, rows: int, seq_len: int, pairs_a_row: float,
                    backward: bool,
                    bytes_per_value: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one flash-attention call over ``rows`` rows of
    ``seq_len`` in which a row's queries attend ``pairs_a_row`` (query,
    key) pairs — T (T + 1) / 2 for a causal row of one document, less
    where documents are packed and do not see each other. The forward
    multiplies scores and values (2 x 2 x hd a pair a head) and reads
    q, k, v and writes the output once; the backward recomputes the
    scores and makes three gradients (five matmuls against the
    forward's two) and reads q, k, v, the output and its gradient and
    writes three gradients."""
    H, KVH, hd = d["H"], d["KVH"], d["hd"]
    fwd = 4.0 * rows * H * hd * pairs_a_row
    q_bytes = rows * seq_len * H * hd * bytes_per_value
    kv_bytes = rows * seq_len * KVH * hd * bytes_per_value
    if not backward:
        return fwd, 2 * q_bytes + 2 * kv_bytes
    return 2.5 * fwd, 4 * q_bytes + 4 * kv_bytes
