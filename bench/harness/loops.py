"""The closed and the open loop, and the arithmetic of their metrics.

Both loops call ``feed(batch)`` (the session under test) from this one
thread and log every call on the host clock.  The arithmetic works on
those logs alone, so the tests check it on logs they write themselves.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Iterable, List, Optional, Sequence, Sized, Tuple

import numpy as np

__all__ = ["FeedLog", "run_closed", "run_open", "closed_rate",
           "open_latencies"]


@dataclasses.dataclass
class FeedLog:
    n: int            # tuples in the feed
    start: float      # host clock when the call began
    end: float        # host clock when it returned
    cycle_end: bool   # the feed filled a window: its pane flush ran inside
    due: float = 0.0  # open loop: when its last tuple was created


def run_closed(feed: Callable, batches: Iterable[Tuple[Sized, bool]],
               seconds: float, *, clock=time.perf_counter,
               after_feed: Optional[Callable] = None):
    """Feed back to back until the first window cycle that ends at least
    ``seconds`` after the start.  ``batches`` yields (batch, whether the
    batch ends a window).  Returns (logs, t0)."""
    logs: List[FeedLog] = []
    t0 = clock()
    for b, ce in batches:
        s = clock()
        feed(b)
        e = clock()
        logs.append(FeedLog(len(b), s, e, ce))
        if after_feed is not None:
            after_feed(logs[-1])
        if ce and e - t0 >= seconds:
            return logs, t0
    raise RuntimeError("the stream ran out before the window closed")


def closed_rate(logs: Sequence[FeedLog], t0: float):
    """(tuples, seconds, cycles) over the whole window cycles in ``logs``:
    from ``t0`` to the return of the last feed that ended a cycle."""
    last = max((i for i, g in enumerate(logs) if g.cycle_end), default=-1)
    if last < 0:
        return 0, 0.0, 0
    tuples = sum(g.n for g in logs[:last + 1])
    cycles = sum(1 for g in logs[:last + 1] if g.cycle_end)
    return tuples, logs[last].end - t0, cycles


def run_open(feed: Callable, batches: Iterable[Tuple[Sized, bool]],
             rate: float, seconds: float, *, clock=time.perf_counter,
             sleep=time.sleep, after_feed: Optional[Callable] = None):
    """Tuple ``i`` is created at ``t0 + i / rate``; a feed is due when its
    last tuple is created and is sent then, or at once if the loop is
    behind.  Feeds stop at ``t0 + seconds``.  Returns (logs, t0, t_end)."""
    logs: List[FeedLog] = []
    t0 = clock()
    t_end = t0 + seconds
    first = 0
    for b, ce in batches:
        due = t0 + (first + len(b) - 1) / rate
        first += len(b)
        if due > t_end:
            return logs, t0, t_end
        now = clock()
        if now >= t_end:
            return logs, t0, t_end
        if due > now:
            sleep(due - now)
        s = clock()
        feed(b)
        e = clock()
        logs.append(FeedLog(len(b), s, e, ce, due))
        if after_feed is not None:
            after_feed(logs[-1])
    raise RuntimeError("the stream ran out before the window closed")


def open_latencies(logs: Sequence[FeedLog], rate: float, t0: float,
                   t_end: float) -> np.ndarray:
    """Per tuple created in [t0, t_end]: from its creation to the return
    of the feed that carried it.  A tuple whose feed had not returned by
    ``t_end`` (unsent, running, or not yet full) counts the time it had
    waited by then."""
    n_due = int(math.floor((t_end - t0) * rate)) + 1
    ret = np.full(n_due, t_end)
    pos = 0
    for g in logs:
        hi = min(pos + g.n, n_due)
        if hi > pos:
            ret[pos:hi] = min(g.end, t_end)
        pos += g.n
    created = t0 + np.arange(n_due) / rate
    return ret - created
