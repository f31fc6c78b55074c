"""Session front end (``SimulatorSession.feed``): milliseconds per feed
spent in the ``session.feed`` span outside every ``fused.*`` span inside
it.  Source: the program's tracer spans."""

from harness.spans import covered, inside, named


def read(b):
    spans = inside(b["spans"], b["window"])
    feeds = named(spans, "session.feed")
    if not feeds:
        return None
    fused = [s for s in b["spans"] if s[0].startswith("fused.")]
    own = sum((f[2] - f[1]) - covered(f, fused) for f in feeds)
    return own / len(feeds) * 1e3
