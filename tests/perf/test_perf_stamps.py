"""What a serving run reads from the answers' stamps, on hand-made
records: which tokens count for ``serve_tok_s``, that a whole answer
with unreadable stamps is malformed and not a smaller count, that the
step's mfu and the decode roofline read the in-flight answers' tokens,
and the waits a streaming tenant would feel."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import flops, harness, stamps  # noqa: E402

T0, WINDOW = 100.0, 10.0            # the window is [100, 110]
D = {"L": 2, "D": 64, "H": 4, "KVH": 2, "hd": 16, "F": 128, "V": 256}


def _rec(stamps_at, *, prompt_len=8, due_s=0.0, sent_s=None, whole=True,
         admitted=None):
    """A record as ``perf/kinds/serve.py`` keeps it; sent when due, or
    just before its first stamp where that is earlier."""
    n = len(stamps_at)
    if sent_s is None:
        sent_s = min(due_s, stamps_at[0] - T0 - 0.001)
    return {"due_s": due_s, "sent_s": sent_s,
            "prompt_len": prompt_len, "max_new_tokens": n,
            "tokens": list(range(1, n + 1)) if whole else None,
            "error": None if whole else "shed: rate",
            # the client reads its clock a little after the last stamp
            "done_s": (stamps_at[-1] - T0 + 0.001) if whole else None,
            "timeline": {"t_submitted": T0 + due_s,
                         "t_admitted": (stamps_at[0] - 0.01
                                        if admitted is None else admitted),
                         "t_first_token": stamps_at[0],
                         "t_tokens": list(stamps_at),
                         "t_finished": stamps_at[-1]} if whole else None}


@pytest.mark.parametrize("stamps_at, whole, want, span", [
    # an answer whole inside the window: all of its tokens
    ([101.0, 102.0, 103.0, 104.0], True, 4, (0, 4, True)),
    # one straddling the close: the tokens it had by then
    ([108.0, 109.0, 109.5, 110.5, 111.0], True, 3, (0, 3, True)),
    # one wholly after the close: none
    ([110.5, 111.0, 112.0], True, 0, None),
    # one that failed: none, whatever it had been sent
    ([101.0, 102.0], False, 0, None),
    # stamps equal to either edge are inside
    ([100.0, 105.0, 110.0], True, 3, (0, 3, True)),
    # one straddling the open: its later tokens, and no prefill
    ([98.0, 99.0, 100.5, 101.0], True, 2, (2, 4, False)),
], ids=["whole-inside", "straddles-close", "after-close", "failed",
        "on-the-edges", "straddles-open"])
def test_tokens_are_counted_where_they_were_stamped(stamps_at, whole, want,
                                                    span):
    got = stamps.in_window([_rec(stamps_at, whole=whole)], T0, WINDOW)
    assert got["tokens"] == want and got["malformed"] == []
    if span is None:
        assert got["spans"] == []
    else:
        assert got["spans"] == [{"prompt_len": 8, "lo": span[0],
                                 "hi": span[1], "prefill": span[2]}]


def test_the_count_is_the_sum_over_requests():
    records = [_rec([101.0, 102.0, 103.0]), _rec([109.0, 110.5]),
               _rec([111.0]), _rec([101.0], whole=False)]
    got = stamps.in_window(records, T0, WINDOW)
    assert got["tokens"] == 4 and len(got["spans"]) == 2
    # over the window it is the metric
    assert got["tokens"] / WINDOW == pytest.approx(0.4)


def _missing(rec):
    rec["timeline"]["t_tokens"].pop()


def _unordered(rec):
    t = rec["timeline"]["t_tokens"]
    t[1], t[2] = t[2], t[1]


def _none(rec):
    rec["timeline"] = None


def _before_the_call(rec):
    # the client called at 103.5, the first stamp says 103.0
    rec["sent_s"] = 3.5


def _after_the_return(rec):
    # the client had the whole answer at 105.5, the last stamp says 106.0
    rec["done_s"] = 5.5


def _all_at_the_finish_on_another_clock(rec):
    # a program that stamps a whole answer at once, on time.time()
    rec["timeline"]["t_tokens"] = [1.7e9] * 4


@pytest.mark.parametrize("spoil, why", [
    (_missing, "3 stamps for 4 tokens"), (_unordered, "do not ascend"),
    (_none, "no timeline"),
    (_before_the_call, "first stamp before the client's call"),
    (_after_the_return, "last stamp after the client's call returned"),
    (_all_at_the_finish_on_another_clock, "last stamp after")],
    ids=["stamp-missing", "out-of-order", "no-timeline", "before-the-call",
         "after-the-return", "another-clock"])
def test_a_whole_answer_with_unreadable_stamps_is_malformed(spoil, why):
    good, bad = _rec([101.0, 102.0]), _rec([103.0, 104.0, 105.0, 106.0])
    spoil(bad)
    got = stamps.in_window([good, bad], T0, WINDOW)
    # named, and not counted as the tokens its stamps still show
    assert len(got["malformed"]) == 1 and why in got["malformed"][0]
    assert got["malformed"][0].startswith("request 1:")
    assert got["tokens"] == 2
    assert stamps.malformed(good, T0) is None


def test_a_whole_request_reads_the_whole_requests_flops():
    """Cut at no edge, the stamped work is ``flops.serve_flops``'."""
    for prompt, new, fresh in [(8, 5, 1.0), (64, 1, 1.0), (40, 12, 0.5)]:
        span = {"prompt_len": prompt, "lo": 0, "hi": new, "prefill": True}
        assert stamps.forward_flops(D, [span], fresh) == pytest.approx(
            flops.serve_flops(D, prompt, new, round(fresh * prompt)))


def test_positions_attended_by_the_decode_tokens():
    # tokens 3, 4, 5 of a 10-token prompt's answer: 13 + 14 + 15
    assert stamps.positions_attended(
        [{"prompt_len": 10, "lo": 3, "hi": 6, "prefill": False}]) == (42.0, 3)
    # token 0 is prefill's: decode tokens 1 and 2 attend 11 + 12
    assert stamps.positions_attended(
        [{"prompt_len": 10, "lo": 0, "hi": 3, "prefill": True}]) == (23.0, 2)
    assert stamps.positions_attended(
        [{"prompt_len": 10, "lo": 0, "hi": 1, "prefill": True}]) == (0.0, 0)


MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def _hand_made_run(records):
    """What ``serve.run`` hands the per-layer readers, by hand: the
    decode program ran four times in the slice, 2 ms each."""
    modules = [(f"jit_paged_decode_step({i})", i * 3 * MS, 2 * MS)
               for i in range(4)]
    return {"dims": D, "window_s": WINDOW, "peaks": PEAKS, "t0": T0,
            "requests": records, "counters": {"decode_steps": 10},
            "stamped": stamps.in_window(records, T0, WINDOW),
            "trace": {"modules": modules, "ops": [], "host": []}}


def _read(name, run):
    cell = {"per_layer": [{"name": name}]}
    return harness.read_per_layer(cell, run).get(name)


@pytest.mark.parametrize("name", ["serve_mfu_pct", "paged_decode_roofline"])
def test_the_readers_see_the_answers_in_flight_at_the_close(name):
    """An answer in flight at the close did decode steps inside the
    window: both readers count its stamped tokens, and no more."""
    inside = _rec([101.0, 102.0, 103.0])
    flying = _rec([108.0, 109.0, 109.5, 110.5, 111.0], prompt_len=20)
    alone = _read(name, _hand_made_run([inside]))
    both = _read(name, _hand_made_run([inside, flying]))
    whole = _read(name, _hand_made_run(
        [inside, _rec([108.0, 109.0, 109.5, 109.6, 109.7], prompt_len=20)]))
    assert alone < both < whole


def test_serve_mfu_pct_on_a_hand_made_run():
    flying = _rec([108.0, 109.0, 109.5, 110.5, 111.0], prompt_len=20)
    # prefill of 20 tokens and decode tokens 1, 2 of the answer
    work = flops.serve_flops(D, 20, 3, 20)
    assert _read("serve_mfu_pct", _hand_made_run([flying])) == pytest.approx(
        100.0 * work / (WINDOW * PEAKS["bf16_flops_per_s"]))
    assert _read("serve_mfu_pct", _hand_made_run(
        [_rec([111.0, 112.0])])) is None      # nothing to read: silent


def test_paged_decode_roofline_on_a_hand_made_run():
    flying = _rec([108.0, 109.0, 109.5, 110.5, 111.0], prompt_len=20)
    run = _hand_made_run([flying])
    got = _read("paged_decode_roofline", run)
    # decode tokens 1 and 2 attend 21 + 22 positions over 10 steps; the
    # slice's first and last run of the program are left out
    least_s = flops.decode_step_bytes(D, 4.3) / PEAKS["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least_s / 0.002)
    note = run["notes"]["paged_decode_roofline"]
    assert note["runs"] == 2
    assert note["positions_attended_a_step"] == pytest.approx(4.3)


def test_what_a_streaming_tenant_would_feel():
    records = [
        _rec([101.0, 101.5, 102.5], due_s=0.5, sent_s=0.6, admitted=100.9),
        _rec([104.0, 104.25], due_s=3.0, admitted=103.5),
        _rec([105.0, 106.0], due_s=4.0, whole=False)]
    felt = stamps.waits(records, T0)
    assert felt["ttft_ms"] == pytest.approx([500.0, 1000.0])
    assert felt["itl_ms"] == pytest.approx([500.0, 1000.0, 250.0])
    # from the call's start (sent_s), not from the due time
    assert felt["queue_ms"] == pytest.approx([300.0, 500.0])
    s = stamps.summary(felt["itl_ms"])
    assert s["n"] == 3 and s["p50"] == pytest.approx(500.0)
    assert stamps.summary([]) == {"p50": None, "p95": None, "n": 0}
    # the tail by name: latency, first token after due, answer tokens
    assert stamps.latency_tail(records, T0, top=1) == [
        pytest.approx([2001.0, 500.0, 3])]    # done_s: 1 ms on


def test_gateway_queue_reads_the_admission_stamps():
    records = [_rec([101.0 + i, 102.0 + i], due_s=float(i),
                    admitted=100.0 + i + 0.1 * i) for i in range(5)]
    got = _read("gateway_queue_ms_p95", _hand_made_run(records))
    assert got == pytest.approx(380.0)      # waits 0, 100, .. 400 ms
    assert _read("gateway_queue_ms_p95", _hand_made_run(
        [_rec([101.0], whole=False)])) is None
