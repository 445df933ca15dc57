"""What a serving run reads from the answers' stamped timelines.

``ServingFleet.submit_and_wait`` returns ``info["timeline"]``: the
engine's ``time.perf_counter()`` stamps of one request
(``t_submitted``, ``t_admitted``, ``t_first_token``, ``t_tokens[]``,
``t_finished``), on the clock the harness times its window with. A
record of ``perf/kinds/serve.py`` keeps it whole. Everything here is
pure arithmetic over such records, so that a test can feed it
hand-made ones: which answer tokens were stamped inside the window,
the work of those tokens, and the waits a streaming tenant would feel.

A record counts only where the answer came back whole (``done_s`` set:
all its tokens, no error). The stamps place tokens the client really
received; they are never the count's only witness: a whole answer
whose stamps are missing, of another number than its tokens, not
ascending, or outside the client's own call (its first before
``submit_and_wait`` was called, its last after the call returned, both
read by the harness on the same clock) is ``malformed`` and fails the
run, it does not shrink the count. The stamps are taken by the program
(``models/generate.py``, beside ``req.tokens.append``), outside the
benchmark's paths: the client's two readings are what holds them.
"""

from bisect import bisect_left, bisect_right

from perf import flops
from perf.traffic_gen import percentile


def malformed(rec: dict, t0: float) -> str | None:
    """Why a whole answer's timeline cannot place its tokens, or None.
    ``t0`` is the instant the record's ``sent_s`` and ``done_s`` count
    from."""
    tl = rec.get("timeline")
    if not tl or tl.get("t_tokens") is None:
        return "no timeline"
    t = tl["t_tokens"]
    if len(t) != len(rec["tokens"]):
        return f"{len(t)} stamps for {len(rec['tokens'])} tokens"
    if any(b < a for a, b in zip(t, t[1:])):
        return "stamps do not ascend"
    if t and t[0] < t0 + rec["sent_s"]:
        return "first stamp before the client's call"
    if t and t[-1] > t0 + rec["done_s"]:
        return "last stamp after the client's call returned"
    return None


def in_window(records: list[dict], t0: float, window_s: float) -> dict:
    """The answer tokens stamped in ``[t0, t0 + window_s]``, both edges
    inside, over every request that came back whole.

    ``tokens`` is their number; ``spans`` says which they were, a whole
    request with anything inside: ``{"prompt_len", "lo", "hi",
    "prefill"}`` for tokens ``lo <= j < hi`` of its answer, ``prefill``
    where its first token (which prefill's logits give) is among them;
    ``malformed`` lists the whole answers whose stamps cannot be read,
    which give no tokens and fail the run. A request that failed or
    never returned gives nothing."""
    end = t0 + window_s
    tokens, spans, bad = 0, [], []
    for i, rec in enumerate(records):
        if rec.get("done_s") is None:
            continue
        why = malformed(rec, t0)
        if why:
            bad.append(f"request {i}: {why}")
            continue
        t = rec["timeline"]["t_tokens"]
        # ascending: the stamps inside are one run lo..hi
        lo, hi = bisect_left(t, t0), bisect_right(t, end)
        if hi > lo:
            tokens += hi - lo
            spans.append({"prompt_len": rec["prompt_len"], "lo": lo,
                          "hi": hi, "prefill": lo == 0})
    return {"tokens": tokens, "spans": spans, "malformed": bad}


def positions_attended(spans: list[dict]) -> tuple[float, int]:
    """(positions, decode tokens): token j >= 1 of an answer comes out
    of a decode step that attends the prompt and the j answer tokens
    before it (itself, just written, among them); token 0 is
    prefill's and attends in no decode step."""
    positions, steps = 0.0, 0
    for s in spans:
        lo, hi = max(s["lo"], 1), s["hi"]
        if hi > lo:
            n = hi - lo
            positions += n * s["prompt_len"] + (lo + hi - 1) * n / 2.0
            steps += n
    return positions, steps


def forward_flops(d: dict, spans: list[dict], fresh: float = 1.0) -> float:
    """Forward work of the stamped tokens, by ``flops.serve_flops``'
    arithmetic cut at the window's edges: the prefill of a request
    whose first token lies inside (``fresh`` of its prompt, the rest
    adopted from the prefix cache), and one fed token a decode token,
    each 2 a matmul parameter, attention 4 x L x H x hd a position
    attended. A request wholly inside reads exactly ``serve_flops``."""
    mm = 2.0 * flops.matmul_params(d)
    attn = 4.0 * d["L"] * d["H"] * d["hd"]
    work = 0.0
    for s in spans:
        if s["prefill"]:
            filled = round(fresh * s["prompt_len"])
            first = s["prompt_len"] - filled
            work += mm * filled + attn * (
                filled * first + filled * (filled + 1) / 2.0)
    positions, steps = positions_attended(spans)
    return work + mm * steps + attn * positions


def summary(values_ms: list[float]) -> dict:
    """Median, 95th percentile and the number of samples."""
    return {"p50": percentile(values_ms, 0.5) if values_ms else None,
            "p95": percentile(values_ms, 0.95) if values_ms else None,
            "n": len(values_ms)}


def waits(records: list[dict], t0: float) -> dict:
    """What a streaming tenant would feel, over every whole answer
    with a sound timeline, as lists of milliseconds: ``ttft_ms`` the
    first token's stamp less the time the request was due, ``itl_ms``
    the gaps between one answer's consecutive stamps, ``queue_ms`` the
    admission stamp less the start of the ``submit_and_wait`` call
    (the gateway's lock and queue, then the engine's queue)."""
    ttft, itl, queue = [], [], []
    for rec in records:
        if rec.get("done_s") is None or malformed(rec, t0):
            continue
        tl = rec["timeline"]
        t = tl["t_tokens"]
        if t:
            ttft.append(1e3 * (t[0] - t0 - rec["due_s"]))
            itl.extend(1e3 * (b - a) for a, b in zip(t, t[1:]))
        if rec.get("sent_s") is not None and tl.get("t_admitted") is not None:
            queue.append(1e3 * max(0.0, tl["t_admitted"] - t0
                                   - rec["sent_s"]))
    return {"ttft_ms": ttft, "itl_ms": itl, "queue_ms": queue}


def latency_tail(records: list[dict], t0: float, top: int = 8) -> list:
    """The ``top`` longest whole answers as ``[latency ms, first token
    after due ms, answer tokens]``, longest first: a tail that moved
    can be followed to the requests that moved it."""
    rows = []
    for rec in records:
        if rec.get("done_s") is None:
            continue
        first = (None if malformed(rec, t0) or not rec["tokens"] else
                 1e3 * (rec["timeline"]["t_tokens"][0] - t0 - rec["due_s"]))
        rows.append([1e3 * (rec["done_s"] - rec["due_s"]), first,
                     len(rec["tokens"])])
    return sorted(rows, key=lambda r: -r[0])[:top]
