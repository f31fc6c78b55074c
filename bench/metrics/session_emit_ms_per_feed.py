"""Window emit: milliseconds per feed in ``session.emit``, where an
operator stage drains its closed windows' partials and builds the
partial-aggregate stream it sends downstream.  Source: the program's
tracer spans."""

from harness.spans import inside, named, total


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    emits = named(spans, "session.emit")
    if not feeds or not emits:
        return None
    return total(emits) / len(feeds) * 1e3
