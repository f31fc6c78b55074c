"""Fused launch, wait: milliseconds per feed in ``fused.segment.readback``,
where the host waits for the launch to finish on the device and copies
the per-worker vectors and finish times back.  Source: the program's
tracer spans."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    if not feeds:
        return None
    return total(named(spans, "fused.segment.readback")) / len(feeds) * 1e3
