"""The one traffic generator: a mix is a data file under
``perf/traffic/``, and this module turns it and ``--seed`` into
requests (kind ``serve``) or packed training batches (kind ``train``).

Every seed gets the same work. The sizes of a mix are not drawn: they
are the quantiles of the mix's distribution at evenly spaced points,
so the multiset of prompt lengths, answer lengths, gaps between
arrivals and document lengths is fixed by the file (and, for arrivals,
by the window's length). For requests the order too is the file's
(``schedule_seed``): a tail latency and the tokens finished before the
window closes depend on which long answers meet and on what is in
flight at the close, and with the order drawn from ``--seed`` the runs
of one commit spread by 8 % and 12 % (my chip runs, PR 29). ``--seed``
draws the token ids, and for training the order of the documents.
"""

import math
from statistics import NormalDist

import numpy as np

IGNORE_INDEX = -100     # the label that the program's loss leaves out


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` whole sizes: the distribution's quantiles at (i + 0.5)/n,
    clipped to the mix's limits. Ascending."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(spec: dict, seconds: float, rng) -> np.ndarray:
    """Due times in [0, seconds) of an open loop at the mix's rate:
    round(rate x seconds) arrivals whose gaps are the exponential
    distribution's quantiles in an order drawn from ``rng``, scaled so
    that they fill the window. Not draws of a Poisson process: its
    gaps' distribution, the same multiset in every run."""
    n = max(1, int(round(spec["rate_per_s"] * seconds)))
    if spec["process"] != "exponential_gap_quantiles":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    rng.shuffle(gaps)
    # each arrival opens its gap: the first is due at 0 and the last
    # gap runs out with the window
    return (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())


def serve_requests(mix: dict, seed: int, seconds: float,
                   vocab: int) -> list[dict]:
    """The window's requests, in the order they are due: ``due_s``,
    ``prompt`` (token ids; ids start at 1, 0 is the pad) and
    ``max_new_tokens``."""
    order = np.random.default_rng(mix["schedule_seed"])
    rng = np.random.default_rng(seed)
    due = arrival_times(mix["arrivals"], seconds, order)
    n = len(due)
    prompts = quantile_sizes(mix["prompt_tokens"], n)
    answers = quantile_sizes(mix["answer_tokens"], n)
    order.shuffle(prompts)
    order.shuffle(answers)
    return [{"due_s": float(t),
             "prompt": rng.integers(1, vocab, int(p)).tolist(),
             "max_new_tokens": int(a)}
            for t, p, a in zip(due, prompts, answers)]


def in_flight_at_close(requests: list[dict], seconds: float,
                       close: dict) -> list[dict]:
    """The requests whose answers a window of ``seconds`` would cut,
    by the mix's own plain rule (its ``close`` block): an answer's
    first token comes ``first_token_s`` after it is due and each
    further one ``ms_per_token`` later. A token count rides on when
    each such answer was let in, so a mix that is to hold
    ``serve_tok_s`` steady states how many its order leaves
    (``in_flight_max``) and a test holds it to that. A shorter step
    can only take answers out of this list; a longer one, another rate
    or another order has to be looked at again."""
    return [r for r in requests
            if r["due_s"] + close["first_token_s"]
            + r["max_new_tokens"] * close["ms_per_token"] / 1e3 > seconds]


def warmup_requests(mix: dict, prompt_lens, slots: int, vocab: int,
                    bucket) -> list[dict]:
    """Requests that touch every program the window's requests reach
    and no other: one prompt of each prefill bucket that
    ``prompt_lens`` (the window's own, the same for every seed) fall
    into, repeated until there are at least two for every slot, so
    that every slot decodes. ``bucket`` is the program's own padding
    rule. Token ids come from a fixed stream: warm-up is the same work
    whatever the seed."""
    rng = np.random.default_rng(0x5eed)
    hi = mix["prompt_tokens"]["max"]
    sizes = sorted({bucket(int(n)) for n in prompt_lens})
    new = max(2, min(8, mix["answer_tokens"]["min"]))
    out = []
    while len(out) < 2 * slots or len(out) < len(sizes):
        n = min(sizes[len(out) % len(sizes)], hi)
        out.append({"due_s": 0.0,
                    "prompt": rng.integers(1, vocab, n).tolist(),
                    "max_new_tokens": new})
    return out


def train_batches(mix: dict, seed: int, vocab: int, seq_len: int,
                  rows: int):
    """Endless stream of packed batches: documents of the mix's
    lengths, concatenated and cut into full rows of ``seq_len``.
    ``positions`` restart at 0 in each document (a document cut by a
    row's end goes on in the next row at the position it had reached),
    ``segments`` number the documents of a row from 1, ``labels`` are
    the next token of the same document and ``IGNORE_INDEX`` at a
    document's last token. No row is padded."""
    rng = np.random.default_rng(seed)
    pool = quantile_sizes(mix["doc_tokens"], int(mix["doc_pool"]))
    rng.shuffle(pool)
    need = rows * seq_len
    d = 0
    left = 0            # tokens of the current document still to place
    at = 0              # position its next token has
    while True:
        tokens = rng.integers(1, vocab, need, dtype=np.int32)
        labels = np.empty(need, np.int32)
        labels[:-1] = tokens[1:]
        labels[-1] = int(rng.integers(1, vocab))
        positions = np.empty(need, np.int32)
        segments = np.empty(need, np.int32)
        i, seg = 0, 0
        while i < need:
            if i % seq_len == 0:
                seg = 0
            if left == 0:
                left, at = int(pool[d % len(pool)]), 0
                d += 1
            take = min(left, seq_len - i % seq_len)
            seg += 1
            positions[i:i + take] = np.arange(at, at + take)
            segments[i:i + take] = seg
            left -= take
            at += take
            i += take
            if left == 0:
                labels[i - 1] = IGNORE_INDEX
        shape = (rows, seq_len)
        yield {"tokens": tokens.reshape(shape),
               "labels": labels.reshape(shape),
               "positions": positions.reshape(shape),
               "segments": segments.reshape(shape)}


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    s = sorted(values)
    if not s:
        return math.nan
    pos = q * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
