"""The train cell's clock, ``perf.kinds.train.Feed``, on a stub of the
step: how far it runs ahead, how the window closes and where the
traced slice lies. No jax program runs here."""

import contextlib
import time
import types

import numpy as np
import pytest

from perf.kinds.train import Feed

SEQ = 8


class Loss:
    """A step's loss that is ready at a set time."""

    def __init__(self, ready_at):
        self.ready_at = ready_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return self


class Tracer:
    def __init__(self, feed_ref):
        self.feed_ref, self.calls = feed_ref, []

    def start(self):
        self.calls.append(("start", max(self.feed_ref[0].done_at)))

    def stop(self):
        self.calls.append(("stop", max(self.feed_ref[0].done_at)))


def _batches():
    while True:
        yield {"segments": np.ones((2, SEQ), np.int32)}


def _drive(mix, seconds, step_s=0.0, slow=None, tracer=False):
    """fit()'s part: take a batch, 'dispatch' its step (a loss ready
    ``step_s`` after the one before it, ``slow`` = (step, seconds) makes
    one take longer), never wait. Returns the feed and the steps
    dispatched."""
    span = types.SimpleNamespace(metrics=[])
    ref = []
    feed = Feed(_batches(), span, mix, seconds,
                Tracer(ref) if tracer else None,
                lambda name: contextlib.nullcontext())
    ref.append(feed)
    free_at = time.perf_counter()
    for _batch in feed(types.SimpleNamespace(programs=0)):
        n = len(span.metrics) + 1
        took = slow[1] if slow and slow[0] == n else step_s
        free_at = max(free_at, time.perf_counter()) + took
        span.metrics.append({"loss": Loss(free_at)})
    return feed, len(span.metrics)


MIX = {"warmup_steps": 2, "in_flight": 4, "check_steps": 1,
       "trace": {"after_steps": 3, "steps": 2}}


def test_every_step_sent_is_waited_for_and_counted():
    feed, sent = _drive(MIX, seconds=0.05, step_s=0.004)
    assert feed.first_window_step == MIX["warmup_steps"] + 1
    assert feed.last_window_step == sent
    window = range(feed.first_window_step, sent + 1)
    assert all(s in feed.done_at and s in feed.tokens for s in window)
    # the clock is read after the last step sent has ended, and that
    # is after the time was up
    assert feed.t_end == feed.done_at[sent] == max(feed.done_at.values())
    assert feed.t_end - feed.t0 >= 0.05
    assert len(feed.kept) == MIX["check_steps"]


def test_the_feed_runs_in_flight_steps_ahead_and_no_more():
    feed, sent = _drive(MIX, seconds=0.2, step_s=0.02)
    ahead = MIX["in_flight"]
    # batch i is handed out once step i - ahead has ended: when the
    # time was found up, that one had and the ahead - 1 after it,
    # the last ones sent, were all that could still be running
    assert feed.t_closed - feed.t0 >= 0.2
    late = [s for s in range(feed.first_window_step, sent + 1)
            if feed.done_at[s] > feed.t_closed]
    assert 1 <= len(late) <= ahead - 1
    assert late == list(range(sent - len(late) + 1, sent + 1))


def test_a_stall_that_runs_to_the_windows_end_counts_as_time():
    # the third step of the window takes 0.3 s where the time is up
    # after 0.03: the feed is stopped by it, and its time counts
    feed, sent = _drive(MIX, seconds=0.03, step_s=0.002,
                        slow=(MIX["warmup_steps"] + 3, 0.3))
    assert sent == MIX["warmup_steps"] + 2 + MIX["in_flight"]
    assert feed.t_end - feed.t0 >= 0.3


def test_a_host_that_comes_late_is_told_by_waits_that_do_not_block():
    # steps of no length: every wait finds its step ended
    feed, _sent = _drive(MIX, seconds=0.02, step_s=0.0)
    assert feed.behind_max >= MIX["in_flight"]


@pytest.mark.parametrize("ahead", [2, 4, 7])
def test_the_traced_slice_is_of_the_steady_state(ahead):
    mix = dict(MIX, in_flight=ahead)
    feed, _sent = _drive(mix, seconds=0.15, step_s=0.002, tracer=True)
    opened = mix["warmup_steps"] + mix["trace"]["after_steps"]
    assert feed.traced == (opened, opened + mix["trace"]["steps"])
    calls = feed.tracer.calls
    # it opens once step traced[0] has ended and closes once traced[1]
    # has, whatever in_flight is (a second stop at the end is harmless)
    assert calls[0] == ("start", feed.traced[0])
    assert calls[1] == ("stop", feed.traced[1])
