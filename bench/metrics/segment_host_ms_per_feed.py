"""Fused launch, host side (``FusedEdgeRunner.begin_feed`` and
``run_segment`` up to the enqueue): milliseconds per feed in the
``fused.begin_feed``, ``fused.segment.prep`` and ``fused.segment.launch``
spans, both edges.  Source: the program's tracer spans."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    if not feeds:
        return None
    host = named(spans, "fused.begin_feed", "fused.segment.prep",
                 "fused.segment.launch")
    return total(host) / len(feeds) * 1e3
