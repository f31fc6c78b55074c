"""Fused launch, host: milliseconds per feed in ``fused.hash_fill``,
where the host SHA-1-hashes the keys its ring-hash cache has not seen
(arg ``keys``), both edges: the FG merge edge hashes its keys under SG
too.  Source: the program's tracer spans; a program without the span
reads None."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    fills = named(spans, "fused.hash_fill")
    if not feeds or not fills:
        return None
    return total(fills) / len(feeds) * 1e3
