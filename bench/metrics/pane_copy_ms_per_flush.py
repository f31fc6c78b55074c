"""Pane flush, copy: milliseconds per flush in the
``fused.pane_flush.copy`` spans, one per plane the flush copies device
to host (``pane_cnt``, ``pane_tab``, ``pane_last``).  Source: the
program's tracer spans."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    flushes = named(spans, "fused.pane_flush")
    copies = named(spans, "fused.pane_flush.copy")
    if not flushes or not copies:
        return None
    return total(copies) / len(flushes) * 1e3
