"""Closed-loop send times and the window's TTFT, gap and throughput
arithmetic, on a scripted token timeline."""
import pytest

from chipbench.lib.timeline import (Record, Timeline, WindowClock,
                                    send_times, window_stats)


def scripted():
    """Two clients.  Requests 0 and 1 are the ramp; the window opens at
    t=2 (request 1's first token) and lasts 10 s.  Request 0 completes at
    t=3 and sends request 2; request 1 completes at t=5 and sends request
    3, which the close cuts before its first token."""
    times = [[1.0, 3.0],            # request 0: budget 2
             [2.0, 4.0, 5.0],       # request 1: budget 3
             [4.5, 6.0, 13.0],      # request 2, sent at 3
             []]                    # request 3, sent at 5, cut
    return Record(times=times, clients=2, completions=[3.0, 5.0],
                  opened_at=2.0)


def test_send_times_follow_completions():
    assert send_times(scripted()) == [None, None, 3.0, 5.0]


def test_window_arithmetic():
    s = window_stats(scripted(), 10.0)
    # request 2 sent at 3, first token at 4.5; request 3 was cut
    assert s.attempted == 1 and s.failed == 0
    assert s.ttft_s == [pytest.approx(1.5)]
    # tokens in (2, 12]: 3.0, 4.0, 5.0, 4.5, 6.0
    assert s.out_tokens == 5
    assert sorted(s.gaps_s) == pytest.approx([1.0, 1.5, 2.0, 2.0])
    assert s.first_tokens == [2]


def test_failed_request_counts_as_attempted_and_failed():
    rec = scripted()
    rec.failed = {3}
    s = window_stats(rec, 10.0)
    assert (s.attempted, s.failed) == (2, 1)


def test_window_opens_at_the_last_clients_first_token():
    clock = WindowClock()
    opened = []
    tl = Timeline(2, clock, on_open=lambda: opened.append(True))
    a, b = tl.list_for(2), tl.list_for(1)
    a.append(7)
    assert clock() == 0.0 and not opened
    b.append(8)
    assert clock.opened_at == b.times[0] and opened == [True]
    assert clock() >= 0.0
    a.append(9)
    assert list(a) == [7, 9] and tl.completions == [b.times[0], a.times[1]]
    assert tl.record().times == [a.times, b.times]


def test_window_that_never_opened_is_an_error():
    rec = scripted()
    rec.opened_at = None
    with pytest.raises(RuntimeError):
        window_stats(rec, 1.0)
