"""The pane flush gathers only the entries its tuples touched.

The device pane table is keyed by (worker, pane slot), the slot a key
got on the host the first time it appeared in the open pane.  Every
valid tuple adds 1 to the count of exactly one entry, so the distinct
pairs the launches recorded are exactly the table's live entries.  Each
case below reads the planes before every flush, builds the entries a
``flatnonzero`` scan of the count plane finds, maps each slot back to its
key, and checks that the flush hands the manager the same entries in the
same order: worker, keys, values, counts and last index, int64 columns,
per worker with keys ascending."""

from __future__ import annotations

import numpy as np
import pytest

from repro.core import MembershipEvent
from repro.data.synthetic import zipf_time_evolving
from repro.kernels import feed_fused
from repro.obs import Telemetry
from repro.state import WindowOp, direct_aggregate
from repro.state.window import KeyedStateManager
from repro.topology import (Edge, ScopedEvent, SimulatorEngine, Source,
                            Stage, Topology, config_for)

SCHEMES = ("sg", "fg", "pkg", "dc", "wc", "fish")
WINDOW = 1_024
WORKERS = 8
RATE = 2e4


def _dense_entries(runner):
    """The entries the dense scan of the open pane finds, in its order."""
    tab = np.asarray(runner.pane_tab)
    last = np.asarray(runner.pane_last)
    assert tab.shape == (runner._w1, runner._slots + 1, 2)
    # slot -> key, from the host's key -> slot map
    slotted = np.flatnonzero(runner._slot_of)
    key_of = np.full(tab.shape[1], -1, np.int64)
    key_of[runner._slot_of[slotted] - 1] = slotted
    cnt = tab[:, :, 1]
    flat = np.flatnonzero(cnt)
    ws, sl = np.divmod(flat, cnt.shape[1])
    ks = key_of[sl]
    assert (ks >= 0).all()  # every live entry sits in an assigned slot
    order = np.lexsort((ks, ws))  # worker-major, keys ascending
    ws, ks, flat = ws[order], ks[order], flat[order]
    vs = tab.reshape(-1, 2)[flat, 0].astype(np.int64)
    cs = tab.reshape(-1, 2)[flat, 1].astype(np.int64)
    starts = np.concatenate(
        [[0], np.flatnonzero(ws[1:] != ws[:-1]) + 1, [ws.shape[0]]])
    return [(int(ws[s]), ks[s:e].astype(np.int64), vs[s:e], cs[s:e],
             int(last[ws[s]]))
            for s, e in zip(starts[:-1].tolist(), starts[1:].tolist())]


def _history(name, n_base):
    """(keys, feed size, events) of one pane history."""
    keys = zipf_time_evolving(n_base, num_keys=3_000, z=1.2, seed=11)
    events = ()
    feed = WINDOW // 2  # feeds end on pane boundaries: steady launches
    if name == "growth":
        # the first feed's keys fit a 64-row table; the second, in the
        # same pane, grows the key capacity while the pane is open
        keys = keys.copy()
        keys[:384] %= 60
        feed = 384
    elif name == "event":
        # a scale-out and a removal, both inside a pane
        events = (
            ScopedEvent("agg", MembershipEvent(
                at=1_500, workers=tuple(range(WORKERS + 2)))),
            ScopedEvent("agg", MembershipEvent(
                at=2_700, workers=tuple(range(1, WORKERS + 2)))))
        feed = 700
    elif name == "partial":
        keys = keys[:2 * WINDOW + 300]  # close() flushes a partial pane
    return keys, feed, events


@pytest.mark.parametrize("history", ("plain", "growth", "event", "partial"))
@pytest.mark.parametrize("scheme", SCHEMES)
def test_gathered_flush_matches_dense_scan(scheme, history, monkeypatch):
    keys, feed, events = _history(history, 4 * WINDOW)
    values = (np.arange(keys.shape[0]) % 9 + 1).astype(np.float64)
    op = WindowOp(agg="sum", value="payload", size=WINDOW)
    topo = Topology(name="gather",
                    stages=(Stage("agg", WORKERS, operator=op),),
                    edges=(Edge("source", "agg", config_for(scheme)),))

    checked = []
    real_flush = feed_fused.FusedEdgeRunner.flush_pane
    real_merge = KeyedStateManager.feed_aggregated

    def flush(self, sink):
        if not (self.has_pane and self.pane_fed):
            return real_flush(self, sink)
        want = _dense_entries(self)
        got = []

        def merge(mgr, n_tuples, entries):
            got.append(list(entries))
            return real_merge(mgr, n_tuples, entries)

        monkeypatch.setattr(KeyedStateManager, "feed_aggregated", merge)
        try:
            real_flush(self, sink)
        finally:
            monkeypatch.setattr(KeyedStateManager, "feed_aggregated",
                                real_merge)
        entries, = got
        assert [e[0] for e in entries] == [e[0] for e in want]
        for (w, ks, vs, cs, last), (w0, ks0, vs0, cs0, last0) in zip(
                entries, want):
            for col, ref in ((ks, ks0), (vs, vs0), (cs, cs0)):
                assert col.dtype == np.int64
                np.testing.assert_array_equal(col, ref)
            assert last == last0
        checked.append(len(entries))

    monkeypatch.setattr(feed_fused.FusedEdgeRunner, "flush_pane", flush)
    tel = Telemetry(enabled=True)
    sess = SimulatorEngine(mode="fused").open(topo, arrival_rate=RATE,
                                              telemetry=tel)
    if events:
        sess.advance(events)
    n_feeds = 0
    for batch in Source(keys, arrival_rate=RATE, values=values).iter_batches(
            batch_size=feed):
        sess.feed(batch)
        n_feeds += 1
    rep = sess.close()

    assert rep.state["agg"]["merged"] == direct_aggregate(keys, op,
                                                          values=values)
    snap = tel.metrics.snapshot()
    flushes = snap["fused.pane_flushes"]["value"]
    assert flushes == len(checked) >= 3 and min(checked) > 0
    # one gather launch per flush, counted apart from the segments
    assert snap["fused.pane_gathers"]["value"] == flushes
    if history in ("plain", "partial"):
        # steady feeds: one segment launch each
        assert snap["fused.dispatches"]["value"] == n_feeds
