"""Host timestamps of a closed-loop serving run, and what they add up to.

The engine hands each token to its request with ``req.generated.append``;
``TimedTokens`` is that list, stamping each append on the host clock.  The
engine has waited for the device before each append (``int(argmax)`` at
admission, ``np.asarray`` after a decode step), so a stamp is the time
the token was complete on the device.

A closed loop of B clients with no think time: the first B requests are
sent at once (the ramp), and the k-th completion sends request B + k.
The window opens when the B-th request has its first token, so that every
slot has been filled once, and closes ``seconds`` later.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np


class WindowClock:
    """The engine's clock: 0 until the window opens, then seconds since.
    ``ServeEngine.run(..., deadline_s=seconds)`` then ends the run once
    the window has lasted ``seconds``."""

    def __init__(self):
        self.opened_at = None

    def __call__(self) -> float:
        if self.opened_at is None:
            return 0.0
        return time.perf_counter() - self.opened_at


class Timeline:
    """Token stamps of one run's requests, in queue order."""

    def __init__(self, clients: int, clock: WindowClock, on_open=None):
        self.clients = clients
        self.clock = clock
        self.on_open = on_open
        self.tokens: list[TimedTokens] = []
        self.completions: list[float] = []
        self.n_first = 0

    def list_for(self, budget: int) -> "TimedTokens":
        tokens = TimedTokens(self, budget)
        self.tokens.append(tokens)
        return tokens

    def _stamp(self, tokens: "TimedTokens", t: float):
        if len(tokens) == 1:
            self.n_first += 1
            if self.n_first == self.clients and self.clock.opened_at is None:
                self.clock.opened_at = t
                if self.on_open is not None:
                    self.on_open()
        if len(tokens) == tokens.budget:
            self.completions.append(t)

    def record(self) -> "Record":
        return Record(
            times=[list(t.times) for t in self.tokens],
            clients=self.clients, completions=list(self.completions),
            opened_at=self.clock.opened_at)


class TimedTokens(list):
    """``Request.generated``: a list that stamps every append."""

    def __init__(self, timeline: Timeline, budget: int):
        super().__init__()
        self.timeline = timeline
        self.budget = budget
        self.times: list[float] = []

    def append(self, tok):
        t = time.perf_counter()
        super().append(tok)
        self.times.append(t)
        self.timeline._stamp(self, t)


@dataclass
class Record:
    """What a closed-loop run leaves: token stamps per request in queue
    order, the completion stamps in order, and when the window opened."""
    times: list[list[float]]
    clients: int
    completions: list[float]
    opened_at: float | None
    failed: set[int] = field(default_factory=set)   # queue indices


def send_times(rec: Record) -> list[float | None]:
    """When each request was sent: None for the ramp (sent at start) and
    for requests no completion sent before the run ended."""
    out: list[float | None] = []
    for i in range(len(rec.times)):
        k = i - rec.clients
        out.append(rec.completions[k] if 0 <= k < len(rec.completions)
                   else None)
    return out


@dataclass
class WindowStats:
    attempted: int
    failed: int
    ttft_s: list[float]          # requests sent in the window
    gaps_s: list[float]          # token gaps ending in the window
    out_tokens: int              # tokens stamped in the window
    first_tokens: list[int]      # queue indices with a first token in it
    window_s: float


def window_stats(rec: Record, seconds: float) -> WindowStats:
    if rec.opened_at is None:
        raise RuntimeError("the window never opened: fewer first tokens "
                           "than clients")
    t0, t1 = rec.opened_at, rec.opened_at + seconds
    sends = send_times(rec)
    attempted = failed = out_tokens = 0
    ttft, gaps, firsts = [], [], []
    for i, times in enumerate(rec.times):
        s = sends[i]
        if s is not None and t0 <= s < t1:
            # a request the close cut before its first token is neither
            if i in rec.failed:
                attempted += 1
                failed += 1
            elif times:
                attempted += 1
                ttft.append(times[0] - s)
        arr = np.asarray(times)
        inside = (arr > t0) & (arr <= t1)
        out_tokens += int(inside.sum())
        if len(arr) and inside[0]:
            firsts.append(i)
        if len(arr) > 1:
            gaps += list(np.diff(arr)[inside[1:]])
    return WindowStats(attempted, failed, ttft, gaps, out_tokens, firsts,
                       seconds)
