"""Pane flush, merge: milliseconds per flush in
``fused.pane_flush.merge``, where the live entries go into the host
window store (``KeyedStateManager.feed_aggregated``).  Source: the
program's tracer spans."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    flushes = named(spans, "fused.pane_flush")
    merges = named(spans, "fused.pane_flush.merge")
    if not flushes or not merges:
        return None
    return total(merges) / len(flushes) * 1e3
