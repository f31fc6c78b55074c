"""Fused launch, host: milliseconds per feed in ``fused.pane_slots``,
where the host gives each key of a launch its slot in the open pane
(args ``new``, keys new to the pane, and ``slots`` in use), both edges.
Source: the program's tracer spans; a program without the span reads
None."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    slots = named(spans, "fused.pane_slots")
    if not feeds or not slots:
        return None
    return total(slots) / len(feeds) * 1e3
