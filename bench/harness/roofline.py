"""Bytes that one fused segment launch needs by its semantics.

Counted from the tuple count and the widths alone, so the same work reads
the same however it is implemented: per tuple its inputs (key, model
time, payload where a window is kept, the key's 32-bit ring hash where
the grouping hashes) and its finish time out; one read-modify-write of
the tuple's (worker, key) pane entry (value and count, int32 each) and of
its replica bit; for FISH also one read-modify-write of the key's
tracker count (float32) and candidate memory (int32).  Per launch the
per-worker vectors go in and out: busy time, service time and count in,
busy time and count out, and FISH's estimator (backlog, assigned and
capacity in, backlog and assigned out), 4 bytes each.  Whole-table
passes the program makes today are not counted: they are not needed.
"""

from __future__ import annotations

__all__ = ["segment_bytes"]

_HASHED = ("fg", "pkg", "dc", "wc", "fish")


def segment_bytes(scheme: str, tuples: int, lanes: int,
                  windowed: bool) -> int:
    per_tuple = 4 + 4 + 4 + 1 * 2          # key, time in; finish out; bit
    if scheme in _HASHED:
        per_tuple += 4
    if windowed:
        per_tuple += 4 + 2 * 8             # payload in; pane entry RMW
    per_lane = 5 * 4
    if scheme == "fish":
        per_tuple += 2 * 4 + 2 * 4         # tracker and memory RMW
        per_lane += 5 * 4
    return per_tuple * tuples + per_lane * lanes
