"""Replica fold, host: milliseconds per feed in ``fused.replica_fold``,
where the host marks each launch's (worker, key) pairs in its bitmap of
the replica pairs seen and keeps the new ones for ``host_sync`` (args
``pairs``, ``new``): at each pane flush, and at each launch of an edge
without a pane (the FG merge edge).  Source: the program's tracer spans;
a program without the span (one that kept the replica matrix on the
device) reads None."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    folds = named(spans, "fused.replica_fold")
    if not feeds or not folds:
        return None
    return total(folds) / len(feeds) * 1e3
