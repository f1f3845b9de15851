"""Host spans of the round driver, on the profiler's clock and the host's.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` -- the same kind
of host span a profiler trace holds for any other code -- and adds the
span's ``time.perf_counter`` duration, in milliseconds, to the open
round's record, under the span's name. A span that opens twice in one
round (the chunked engine stages and dispatches once a chunk) adds up.

``round_span(name)`` opens the round itself: a span that starts a fresh
record, which it closes with the round's own duration. The record is a
plain ``{span name: ms}`` dict; ``CoLearner.run_round`` hands it to the
round's ``RoundLog.host_ms``. The spans a round opens do not overlap
each other, so their times sum to no more than the round's.

``count(name, value)`` adds a program counter to the open round's record
(``RoundRecord.counters``): numbers the round's device work summed and
the round's one fetch brought back.

Finished rounds are also kept, newest last, in a bounded process-wide
history (``recent()``): a reader that never held the learner -- a
benchmark that reads the rounds of its timed window after the learner is
gone -- finds them there by their ``perf_counter`` times.

With no profiler running, a span costs one annotation's enter and exit
and two clock reads.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import time

import jax

#: finished rounds ``recent()`` keeps
HISTORY = 4096

_open: contextvars.ContextVar = contextvars.ContextVar("round_record",
                                                       default=None)
_open_counters: contextvars.ContextVar = contextvars.ContextVar(
    "round_counters", default=None)
_history: collections.deque = collections.deque(maxlen=HISTORY)


@dataclasses.dataclass(frozen=True)
class RoundRecord:
    """One finished round: its ``perf_counter`` start and end, seconds,
    the milliseconds of each span it opened (its own included), and its
    counters."""
    start: float
    end: float
    host_ms: dict
    counters: dict = dataclasses.field(default_factory=dict)


@contextlib.contextmanager
def span(name):
    """A host span named ``name``; its time adds to the open round's
    record, if a round is open."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            record = _open.get()
            if record is not None:
                ms = 1e3 * (time.perf_counter() - t0)
                record[name] = record.get(name, 0.0) + ms


def count(name, value):
    """Add ``value`` to the open round's counter ``name``, if a round is
    open."""
    counters = _open_counters.get()
    if counters is not None:
        counters[name] = counters.get(name, 0) + value


@contextlib.contextmanager
def round_span(name):
    """The span of a whole round: yields the round's fresh record, which
    holds every span opened inside it once the round has closed."""
    record, counters = {}, {}
    token = _open.set(record)
    ctoken = _open_counters.set(counters)
    try:
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            yield record
            t1 = time.perf_counter()
    finally:
        _open.reset(token)
        _open_counters.reset(ctoken)
    record[name] = 1e3 * (t1 - t0)
    _history.append(RoundRecord(t0, t1, record, counters))


def recent():
    """The finished rounds of this process, oldest first (at most
    ``HISTORY``)."""
    return list(_history)
