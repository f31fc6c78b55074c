"""Pane flush, copy: gigabytes per second the flush's device-to-host
copies move, the ``bytes`` of every ``fused.pane_flush.copy`` span over
their seconds.  Source: the program's tracer spans."""

from harness.spans import inside, named, total


def read(b):
    copies = named(inside(b["spans"], b["window"]), "fused.pane_flush.copy")
    secs = total(copies)
    if not copies or secs <= 0.0:
        return None
    return sum((s[3] or {}).get("bytes", 0) for s in copies) / secs / 1e9
