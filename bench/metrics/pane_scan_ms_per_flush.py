"""Pane flush, scan: milliseconds per flush in ``fused.pane_flush.scan``,
where the host finds the live entries of the copied count plane and
splits them per worker.  Source: the program's tracer spans."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    flushes = named(spans, "fused.pane_flush")
    scans = named(spans, "fused.pane_flush.scan")
    if not flushes or not scans:
        return None
    return total(scans) / len(flushes) * 1e3
