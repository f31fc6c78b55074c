"""Pane flush (``FusedEdgeRunner.flush_pane``): share of the traced
window, in percent, spent in ``fused.pane_flush`` spans.  Source: the
program's tracer spans."""

from harness.spans import inside, named, total


def read(b):
    w0, w1 = b["window"]
    flushes = named(inside(b["spans"], b["window"]), "fused.pane_flush")
    if not flushes or w1 <= w0:
        return None
    return total(flushes) / (w1 - w0) * 100.0
