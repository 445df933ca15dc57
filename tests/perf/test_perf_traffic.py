"""The one traffic generator: the same seed gives the same inputs, another
seed the same work with other token ids."""

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import traffic_gen as tg  # noqa: E402

CHAT = json.loads((ROOT / "perf/traffic/chat-steady.json").read_text())
PACKED = json.loads((ROOT / "perf/traffic/packed-4k.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BIG = 2 ** 31 + 12345            # the driver's seeds are large


def test_serve_same_seed_same_requests():
    a = tg.serve_requests(CHAT, BIG, 30.0, 32000)
    b = tg.serve_requests(CHAT, BIG, 30.0, 32000)
    assert a == b


def test_serve_other_seed_same_schedule_other_tokens():
    a = tg.serve_requests(CHAT, 1, 30.0, 32000)
    b = tg.serve_requests(CHAT, 2, 30.0, 32000)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert len(a) == len(b) == round(CHAT["arrivals"]["rate_per_s"] * 30)
    for key in ("due_s", "max_new_tokens"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]


def test_serve_other_schedule_same_sizes_other_order():
    other = dict(CHAT, schedule_seed=CHAT["schedule_seed"] + 1)
    a = tg.serve_requests(CHAT, 1, 30.0, 32000)
    b = tg.serve_requests(other, 1, 30.0, 32000)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    assert sorted(r["max_new_tokens"] for r in a) == sorted(
        r["max_new_tokens"] for r in b)
    gaps = lambda rs: sorted(np.round(np.diff(
        [r["due_s"] for r in rs] + [30.0]), 9))
    assert gaps(a) == gaps(b)


def test_serve_requests_keep_the_mix_limits():
    rs = tg.serve_requests(CHAT, 5, 40.0, 32000)
    p, a = CHAT["prompt_tokens"], CHAT["answer_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in rs)
    assert all(a["min"] <= r["max_new_tokens"] <= a["max"] for r in rs)
    assert all(0 <= r["due_s"] < 40.0 for r in rs)
    assert [r["due_s"] for r in rs] == sorted(r["due_s"] for r in rs)
    assert all(min(r["prompt"]) >= 1 and max(r["prompt"]) < 32000 for r in rs)
    med = np.median([len(r["prompt"]) for r in rs])
    assert abs(med - p["median"]) <= 0.1 * p["median"]
    # no two prompts share their first block
    assert len({tuple(r["prompt"][:16]) for r in rs}) == len(rs)


def test_warmup_reaches_the_windows_buckets_and_every_slot():
    bucket = lambda n: 1 << (n - 1).bit_length()
    lens = [len(r["prompt"]) for r in tg.serve_requests(CHAT, 1, 51.0, 32000)]
    warm = tg.warmup_requests(CHAT, lens, 16, 32000, bucket)
    assert len(warm) >= 32
    # the buckets the window's prompts fall into, and no other
    assert {bucket(len(r["prompt"])) for r in warm} == {
        bucket(n) for n in lens}
    assert warm == tg.warmup_requests(CHAT, lens, 16, 32000, bucket)


@pytest.mark.parametrize("mix,key", [(CHAT, "prompt_tokens"),
                                     (CHAT, "answer_tokens"),
                                     (PACKED, "doc_tokens")])
def test_sizes_keep_the_sources_mean(mix, key):
    """Each length distribution names the mean its public source
    prints; the sizes a window gets keep it within 3 %, clipping and
    rounding included."""
    spec = mix[key]
    sizes = tg.quantile_sizes(
        spec, round(CHAT["arrivals"]["rate_per_s"] * BENCH["run_seconds"])
        if mix is CHAT else PACKED["doc_pool"])
    assert abs(sizes.mean() - spec["source_mean"]) <= 0.03 * spec["source_mean"]
    assert mix["sources"]["lengths"]


def test_the_cell_states_the_rate_its_mix_offers():
    """The rate is the mix file's; the cell's ``why`` states the same
    number. Nothing else of either text is held here: the next sweep
    changes data, not this test."""
    why = next(w["why"] for w in BENCH["workloads"]
               if w["traffic"] == "chat-steady")
    stated = re.search(r"(\d+(?:\.\d+)?) requests/s", why)
    assert stated, why
    assert float(stated.group(1)) == CHAT["arrivals"]["rate_per_s"]


def test_answers_in_flight_at_the_close_by_the_plain_rule():
    close = {"first_token_s": 0.5, "ms_per_token": 100.0}
    requests = [
        {"due_s": 1.0, "max_new_tokens": 80},    # ends at 9.5: inside
        {"due_s": 1.0, "max_new_tokens": 90},    # 10.5: cut by the close
        {"due_s": 9.0, "max_new_tokens": 5},     # exactly 10.0: inside
        {"due_s": 9.8, "max_new_tokens": 1}]     # its first token is late
    assert tg.in_flight_at_close(requests, 10.0, close) == [requests[1],
                                                            requests[3]]
    # a shorter step only takes answers out
    faster = dict(close, ms_per_token=50.0)
    assert tg.in_flight_at_close(requests, 10.0, faster) == [requests[3]]


def test_the_mix_s_order_leaves_a_quiet_close():
    """``serve_tok_s`` rides on when each answer in flight at the close
    was let in: the mix states how many its order leaves by its own
    plain rule, and the schedule the cell runs keeps to it."""
    close = CHAT["close"]
    requests = tg.serve_requests(CHAT, 1, float(BENCH["run_seconds"]), 32000)
    cut = tg.in_flight_at_close(requests, float(BENCH["run_seconds"]), close)
    assert len(cut) <= close["in_flight_max"]


@pytest.mark.parametrize("spec,error", [
    ({"dist": "fixed", "value": 8, "min": 1, "max": 9}, "size distribution"),
    ({"process": "poisson", "rate_per_s": 1.0}, "arrival process")])
def test_unknown_shapes_are_refused(spec, error):
    with pytest.raises(ValueError, match=error):
        if "dist" in spec:
            tg.quantile_sizes(spec, 4)
        else:
            tg.arrival_times(spec, 10.0, np.random.default_rng(0))


@pytest.mark.parametrize("seed", [0, BIG])
def test_train_batches_same_seed_same_batches(seed):
    a = tg.train_batches(PACKED, seed, 32000, 512, 4)
    b = tg.train_batches(PACKED, seed, 32000, 512, 4)
    for _ in range(3):
        x, y = next(a), next(b)
        assert all(np.array_equal(x[k], y[k]) for k in x)


def test_train_batches_other_seed_other_rows_same_sizes():
    a = next(tg.train_batches(PACKED, 1, 32000, 512, 4))
    b = next(tg.train_batches(PACKED, 2, 32000, 512, 4))
    assert not np.array_equal(a["tokens"], b["tokens"])
    pool = lambda s: sorted(tg.quantile_sizes(PACKED["doc_tokens"],
                                              PACKED["doc_pool"]))
    assert pool(1) == pool(2)


def test_train_batch_layout():
    g = tg.train_batches(PACKED, 7, 1000, 256, 4)
    first, second = next(g), next(g)
    for b in (first, second):
        assert b["tokens"].shape == (4, 256)
        assert (b["segments"] >= 1).all()           # no row is padded
        for r in range(4):
            seg, pos = b["segments"][r], b["positions"][r]
            lab, tok = b["labels"][r], b["tokens"][r]
            starts = np.flatnonzero(np.diff(seg)) + 1
            assert (np.diff(seg) >= 0).all() and seg[0] == 1
            # positions run on inside a document, restart at its start
            inside = np.ones(256, bool)
            inside[0] = False
            inside[starts] = False
            assert (pos[inside] == pos[np.flatnonzero(inside) - 1] + 1).all()
            assert (pos[starts] == 0).all()
            # the label is the next token, or nothing at a document's end
            ends = starts - 1
            assert (lab[ends] == tg.IGNORE_INDEX).all()
            mid = np.setdiff1d(np.arange(255), ends)
            assert (lab[mid] == tok[mid + 1]).all()
    # rows all differ
    rows = np.concatenate([first["tokens"], second["tokens"]])
    assert len({r.tobytes() for r in rows}) == 8


def test_percentile_is_numpys():
    v = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    assert tg.percentile(v, 0.95) == pytest.approx(np.percentile(v, 95))
