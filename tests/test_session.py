"""Streaming session API (ISSUE 5): incremental record-batch execution.

The equivalence contract:

* **one batch ≡ one shot** — opening a session, feeding the whole stream
  as a single :class:`RecordBatch` and closing is *bit-identical* to
  ``Engine.run`` (same ``TopologyReport.to_dict()``) for all six schemes
  on both engines — ``run`` literally is open/advance/feed/close.
* **many batches ≈ one shot** — cutting the stream into several feeds is
  exact for the stateless/sequentially-exact schemes (SG/FG/PKG: carried
  FIFO backlog + carried grouper counters reproduce the same routing and
  finish times up to float association) and bounded-drift for the
  epoch-paced schemes (DC/WC/FISH: feed boundaries shift epoch sub-chunk
  boundaries, like any other segmentation change — DESIGN.md §6 bands).
* **time addressing** — an ``at_time`` event lands on the same segment cut
  as the equivalent index event.
* **payloads** — a ``WindowOp(value="payload")`` aggregates the stream's
  real ``values`` column; merged windows match a direct NumPy aggregation.
"""

import numpy as np
import pytest

from repro.core import CapacityEvent, MembershipEvent, at_time
from repro.data.synthetic import record_batches, zipf_time_evolving
from repro.state import KeyedStateManager, WindowOp, direct_aggregate
from repro.topology import (Edge, RecordBatch, ScopedEvent,
                            ServingTopologyEngine, SimulatorEngine, Source,
                            Stage, Topology, WindowOp as TopoWindowOp,
                            config_for, hashed_fanout)

from repro.analysis.contracts import (DRIFT_SCHEMES, EXACT_SCHEMES,
                                      SCHEMES)


@pytest.fixture(scope="module")
def keys():
    return zipf_time_evolving(6_000, num_keys=600, z=1.4, seed=0)


def _single(scheme, workers=8, cost=None, operator=None):
    return Topology(
        name=f"s-{scheme}",
        stages=(Stage("worker", workers, cost=cost, operator=operator),),
        edges=(Edge("source", "worker", config_for(scheme)),),
    )


def _word_count(scheme, cost=None):
    return Topology(
        name="wc",
        stages=(Stage("split", 5, cost=cost,
                      transform=hashed_fanout(3, 300)),
                Stage("count", 7, cost=cost)),
        edges=(Edge("source", "split", config_for("sg")),
               Edge("split", "count", config_for(scheme))),
    )


def _session_run(engine, topo, source, events=(), feeds=1):
    session = engine.open(topo, arrival_rate=source.arrival_rate)
    if events:
        session.advance(events)
    n = int(source.keys.shape[0])
    for batch in source.iter_batches(batch_size=-(-n // feeds)):
        session.feed(batch)
    return session.close()


# ---------------------------------------------------------------------------
# one-batch session == run(), bit-identical (acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", SCHEMES)
def test_one_batch_session_bit_identical_to_run_simulator(scheme, keys):
    topo = _word_count(scheme)
    src = Source(keys, arrival_rate=2e4)
    n_count = keys.shape[0] * 3
    events = [ScopedEvent("count", MembershipEvent(at=n_count // 2,
                                                   workers=tuple(range(6)))),
              ScopedEvent("count", CapacityEvent(at=2 * n_count // 3,
                                                 capacities={0: 4e-3}))]
    eng = SimulatorEngine()
    assert (_session_run(eng, topo, src, events).to_dict()
            == eng.run(topo, src, events).to_dict())


@pytest.mark.parametrize("scheme", SCHEMES)
def test_one_batch_session_bit_identical_to_run_serving(scheme, keys):
    topo = _word_count(scheme)
    src = Source(keys, arrival_rate=2e4)
    events = [ScopedEvent("count", MembershipEvent(at=48,
                                                   workers=tuple(range(6))))]
    eng = ServingTopologyEngine(max_requests=64)
    assert (_session_run(eng, topo, src, events).to_dict()
            == eng.run(topo, src, events).to_dict())


def test_one_batch_session_bit_identical_reference_mode(keys):
    topo = _word_count("fish")
    src = Source(keys, arrival_rate=2e4)
    eng = SimulatorEngine(mode="reference")
    assert (_session_run(eng, topo, src).to_dict()
            == eng.run(topo, src).to_dict())


def test_one_batch_session_bit_identical_with_operator_state(keys):
    op = TopoWindowOp(agg="count", size=1_000)
    topo = Topology(name="op", stages=(
        Stage("count", 6, operator=op), Stage("merge", 4)),
        edges=(Edge("source", "count", config_for("fish")),
               Edge("count", "merge", config_for("fg"))))
    src = Source(keys, arrival_rate=2e4)
    events = [ScopedEvent("count", MembershipEvent(at=2_500,
                                                   workers=tuple(range(5))))]
    for eng in (SimulatorEngine(), ServingTopologyEngine(max_requests=64)):
        assert (_session_run(eng, topo, src, events).to_dict()
                == eng.run(topo, src, events).to_dict())


# ---------------------------------------------------------------------------
# multi-batch feeding vs the one-shot oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
@pytest.mark.parametrize("feeds", (2, 5))
def test_multi_batch_exact_for_sequential_schemes(scheme, feeds, keys):
    # explicit stage costs: capacity defaults are frozen at first feed, so
    # only cost-pinned stages are comparable across batch segmentations
    topo = _word_count(scheme, cost=1e-4)
    src = Source(keys, arrival_rate=2e4)
    one = SimulatorEngine().run(topo, src)
    many = _session_run(SimulatorEngine(), topo, src, feeds=feeds)
    for eo, em in zip(one.edges, many.edges):
        assert em.n_tuples == eo.n_tuples
        assert em.memory_overhead == eo.memory_overhead, eo.edge
        for field, v in eo.row().items():
            assert em.row()[field] == pytest.approx(v, rel=1e-9), \
                (eo.edge, field)
    assert many.e2e_latency_p99 == pytest.approx(one.e2e_latency_p99,
                                                 rel=1e-9)
    assert many.total_time == pytest.approx(one.total_time, rel=1e-9)


@pytest.mark.parametrize("scheme", DRIFT_SCHEMES)
def test_multi_batch_bounded_drift_for_epoch_schemes(scheme, keys):
    topo = _word_count(scheme, cost=1e-4)
    src = Source(keys, arrival_rate=2e4)
    one = SimulatorEngine().run(topo, src)
    many = _session_run(SimulatorEngine(), topo, src, feeds=4)
    for eo, em in zip(one.edges, many.edges):
        assert em.execution_time == pytest.approx(eo.execution_time,
                                                  rel=0.05), eo.edge
        assert em.throughput == pytest.approx(eo.throughput, rel=0.05)
        assert em.memory_overhead == pytest.approx(eo.memory_overhead,
                                                   rel=0.25)
        # load balance must not degrade materially vs the one-shot run
        assert em.imbalance <= eo.imbalance + 0.05, eo.edge
        assert em.latency_p99 <= max(eo.latency_p99 * 10.0, 0.05)
    assert many.total_time == pytest.approx(one.total_time, rel=0.05)


def test_multi_batch_serving_drains_every_feed(keys):
    topo = _word_count("fish")
    src = Source(keys, arrival_rate=2e4)
    eng = ServingTopologyEngine(max_requests=48)
    rep = _session_run(eng, topo, src, feeds=3)
    # each feed is subsampled independently, then fully drained
    assert rep.n_source_tuples == 3 * 48
    assert sum(e.dropped for e in rep.edges) == 0
    assert rep.edge("count").n_tuples == 3 * 48 * 3


def test_event_straddling_feed_boundary_fires_once(keys):
    """A membership event whose index lands inside a later feed fires in
    that feed — and the remap accounting sees exactly one event."""
    topo = _single("fg")
    src = Source(keys, arrival_rate=2e4)
    ev = [ScopedEvent("worker",
                      MembershipEvent(at=4_000, workers=tuple(range(6))))]
    rep = _session_run(SimulatorEngine(), topo, src, ev, feeds=3)
    er = rep.edge("worker")
    assert len(er.remap_events) == 1
    assert er.remap_events[0]["at"] == 4_000  # reported stream-global
    assert 0.0 < er.remap_frac_mean < 0.5


# ---------------------------------------------------------------------------
# time-addressed events
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("feeds", (1, 3))
def test_at_time_lands_on_same_cut_as_index_event(feeds, keys):
    topo = _single("fg")
    src = Source(keys, arrival_rate=2e4)
    j = 4_321
    t = j * (1.0 / 2e4)  # tuple j's timestamp, as the source computes it
    by_index = [ScopedEvent("worker",
                            MembershipEvent(at=j, workers=tuple(range(6))))]
    by_stamp = [ScopedEvent("worker",
                            at_time(MembershipEvent(workers=tuple(range(6))),
                                    t))]
    eng = SimulatorEngine()
    assert (_session_run(eng, topo, src, by_stamp, feeds=feeds).to_dict()
            == _session_run(eng, topo, src, by_index, feeds=feeds).to_dict())


def test_at_time_capacity_event_through_run(keys):
    """``run`` resolves time-addressed events too (one-shot path), and
    capacity events support the same addressing."""
    topo = _single("fish")
    src = Source(keys, arrival_rate=2e4)
    j = 3_000
    slow = {0: 8e-3}
    eng = SimulatorEngine()
    r_idx = eng.run(topo, src,
                    [ScopedEvent("worker", CapacityEvent(at=j,
                                                         capacities=slow))])
    r_t = eng.run(topo, src,
                  [ScopedEvent("worker",
                               at_time(CapacityEvent(capacities=slow),
                                       j * (1.0 / 2e4)))])
    assert r_t.to_dict() == r_idx.to_dict()


def test_at_time_past_stream_end_never_fires(keys):
    topo = _single("fg")
    src = Source(keys, arrival_rate=2e4)
    ev = [ScopedEvent("worker",
                      at_time(MembershipEvent(workers=(0, 1)), 1e9))]
    rep = _session_run(SimulatorEngine(), topo, src, ev, feeds=2)
    assert rep.edge("worker").remap_events == []


# ---------------------------------------------------------------------------
# payload-carrying sources
# ---------------------------------------------------------------------------


def test_payload_sum_matches_numpy_direct_aggregation():
    rng = np.random.default_rng(3)
    n, size = 4_000, 500
    keys = rng.integers(0, 97, n).astype(np.int32)
    values = rng.integers(1, 1_000, n).astype(np.float64)
    op = TopoWindowOp(agg="sum", size=size, value="payload")
    topo = _single("fg", operator=op)
    rep = SimulatorEngine().run(
        topo, Source(keys, arrival_rate=2e4, values=values))
    merged = rep.state["worker"]["merged"]
    for start in range(0, n, size):
        ks = keys[start:start + size].astype(np.int64)
        vs = values[start:start + size].astype(np.int64)
        expect = {}
        for k, v in zip(ks.tolist(), vs.tolist()):
            expect[k] = expect.get(k, 0) + v
        assert merged[start] == expect, start
    # the oracle helper accepts the payload column too
    assert merged == direct_aggregate(keys, op, values=values)


@pytest.mark.parametrize("scheme", ("sg", "fish"))
def test_payload_sum_exact_across_feeds_and_churn(scheme):
    rng = np.random.default_rng(7)
    n = 6_000
    keys = rng.integers(0, 300, n).astype(np.int32)
    values = rng.integers(1, 50, n).astype(np.float64)
    op = TopoWindowOp(agg="sum", size=1_000, value="payload")
    topo = _single(scheme, operator=op)
    src = Source(keys, arrival_rate=2e4, values=values)
    ev = [ScopedEvent("worker",
                      MembershipEvent(at=2_500, workers=tuple(range(7))))]
    rep = _session_run(SimulatorEngine(), topo, src, ev, feeds=4)
    assert (rep.state["worker"]["merged"]
            == direct_aggregate(keys, op, values=values))


def test_payload_op_without_values_column_raises():
    op = TopoWindowOp(agg="sum", size=100, value="payload")
    topo = _single("fg", operator=op)
    with pytest.raises(ValueError, match="payload"):
        SimulatorEngine().run(
            topo, Source(np.arange(500, dtype=np.int32),
                         arrival_rate=1e4))


def test_values_propagate_through_transform_stages():
    """A split stage's emitted tuples inherit the parent payload, so a
    downstream payload-sum operator aggregates fanout copies."""
    n, fanout = 900, 3
    keys = np.arange(n, dtype=np.int32) % 11
    values = np.ones(n, dtype=np.float64) * 5
    op = TopoWindowOp(agg="sum", size=n * fanout, value="payload")
    topo = Topology(
        name="vp",
        stages=(Stage("split", 4, transform=hashed_fanout(fanout, 40)),
                Stage("count", 6, operator=op)),
        edges=(Edge("source", "split", config_for("sg")),
               Edge("split", "count", config_for("fg"))),
    )
    rep = SimulatorEngine().run(
        topo, Source(keys, arrival_rate=1e4, values=values))
    merged = rep.state["count"]["merged"]
    total = sum(v for w in merged.values() for v in w.values())
    assert total == int(values.sum()) * fanout


# ---------------------------------------------------------------------------
# record-batch plumbing and validation
# ---------------------------------------------------------------------------


def test_record_batch_validation():
    with pytest.raises(TypeError, match="integer"):
        RecordBatch(np.array(["a", "b"], dtype=object), np.zeros(2))
    with pytest.raises(ValueError, match="shape"):
        RecordBatch(np.arange(3, dtype=np.int32), np.zeros(2))
    with pytest.raises(ValueError, match="nondecreasing"):
        RecordBatch(np.arange(3, dtype=np.int32),
                    np.array([0.0, 2.0, 1.0]))
    with pytest.raises(ValueError, match="shape"):
        RecordBatch(np.arange(3, dtype=np.int32), np.zeros(3),
                    values=np.zeros(4))
    b = RecordBatch(np.arange(3, dtype=np.int32), np.arange(3) * 0.1,
                    values=np.ones(3))
    assert len(b) == 3
    assert not b.keys.flags.writeable  # frozen columns
    assert not b.values.flags.writeable


def test_source_forms_and_validation(keys):
    with pytest.raises(ValueError, match="exactly one"):
        Source()
    with pytest.raises(ValueError, match="exactly one"):
        Source(keys, batches=iter(()))
    with pytest.raises(TypeError, match="RecordBatch"):
        Source(batches=(np.arange(3),)).iter_batches().__next__()
    # array form splits on the uniform grid and round-trips the stream
    src = Source(keys, arrival_rate=2e4)
    batches = list(src.iter_batches(batch_size=1_024))
    assert sum(len(b) for b in batches) == keys.shape[0]
    np.testing.assert_array_equal(
        np.concatenate([b.keys for b in batches]), keys)
    ts = np.concatenate([b.timestamps for b in batches])
    np.testing.assert_array_equal(ts,
                                  np.arange(keys.shape[0]) * (1.0 / 2e4))
    # batch form rejects per-source columns
    with pytest.raises(ValueError, match="inside each RecordBatch"):
        Source(batches=batches, values=np.ones(3))


def test_session_misuse_raises(keys):
    eng = SimulatorEngine()
    topo = _single("fg")
    session = eng.open(topo)
    with pytest.raises(TypeError, match="RecordBatch"):
        session.feed(keys)
    with pytest.raises(ValueError, match="no stage named"):
        session.advance([ScopedEvent("nope",
                                     MembershipEvent(at=0, workers=(0,)))])
    with pytest.raises(ValueError, match="no address"):
        # the at=-1 default means "address me via at_time()" — forgetting
        # the wrapper must not silently drop the event
        session.advance([ScopedEvent("worker",
                                     MembershipEvent(workers=(0, 1)))])
    with pytest.raises(ValueError, match="batch_size must be positive"):
        list(Source(keys, arrival_rate=2e4).iter_batches(batch_size=-2))
    session.feed(RecordBatch(keys[:100], np.arange(100) * 1e-4))
    with pytest.raises(ValueError, match="time-ordered"):
        session.feed(RecordBatch(keys[:100], np.arange(100) * 1e-6))
    session.close()
    with pytest.raises(RuntimeError, match="closed"):
        session.feed(RecordBatch(keys[:10], np.arange(10) * 1.0))
    with pytest.raises(RuntimeError, match="closed"):
        session.close()


def test_record_batches_adapter_replays_token_stream():
    batches = list(record_batches(num_docs=700, num_keys=50, doc_len=8,
                                  vocab_size=64, batch=256,
                                  arrival_rate=1e4, seed=0))
    assert [len(b) for b in batches] == [256, 256, 188]
    ts = np.concatenate([b.timestamps for b in batches])
    assert np.all(np.diff(ts) > 0)  # one uniform grid across batches
    for b in batches:
        assert b.keys.dtype == np.int32
        assert b.values is not None
        assert np.all(b.values == np.rint(b.values))  # integral payloads
    # the Table-2 proxy replays end to end through a payload-sum session
    op = TopoWindowOp(agg="sum", size=200, value="payload")
    eng = SimulatorEngine()
    session = eng.open(_single("fish", operator=op), arrival_rate=1e4)
    for b in batches:
        session.feed(b)
    rep = session.close()
    all_keys = np.concatenate([b.keys for b in batches])
    all_vals = np.concatenate([b.values for b in batches])
    assert (rep.state["worker"]["merged"]
            == direct_aggregate(all_keys, op, values=all_vals))
    assert rep.n_source_tuples == 700


# ---------------------------------------------------------------------------
# pane-based sliding windows (ROADMAP item): exactness regression
# ---------------------------------------------------------------------------


def _brute_force_partials(keys, workers, op):
    """The pre-pane per-(window, worker) semantics, computed directly: for
    every sliding window, each worker's aggregate over its routed tuples."""
    n = keys.shape[0]
    out = {}
    for start in range(0, n, op.stride):
        lo, hi = start, min(start + op.size, n)
        for i in range(lo, hi):
            k, w = int(keys[i]), int(workers[i])
            d = out.setdefault((start, w), {})
            d[k] = d.get(k, 0) + 1
    return out


def test_pane_composition_matches_per_window_semantics():
    rng = np.random.default_rng(11)
    n = 3_000
    keys = rng.integers(0, 120, n).astype(np.int64)
    workers = rng.integers(0, 5, n).astype(np.int64)
    op = WindowOp(agg="count", size=800, slide=200)
    mgr = KeyedStateManager(op)
    for lo in range(0, n, 700):  # uneven chunks across pane boundaries
        mgr.feed(keys[lo:lo + 700], workers[lo:lo + 700])
    mgr.finalize()
    got = {(p.window, p.worker): dict(zip(p.keys.tolist(),
                                          p.values.tolist()))
           for p in mgr.partials}
    assert got == _brute_force_partials(keys, workers, op)
    # pane layout: live entries are bounded by the tuples inside the
    # retained panes (each tuple counted once), not by every open window's
    # full key set (the per-window layout held each key size/slide times)
    assert (mgr.state_bytes_peak
            <= (op.size // op.stride + 1) * op.stride * 12)


def test_pane_sliding_windows_exact_under_churn_multi_feed(keys):
    op = TopoWindowOp(agg="count", size=2_000, slide=500)
    topo = _single("fish", operator=op)
    src = Source(keys, arrival_rate=2e4)
    ev = [ScopedEvent("worker",
                      MembershipEvent(at=2_300, workers=tuple(range(7))))]
    rep = _session_run(SimulatorEngine(), topo, src, ev, feeds=5)
    st = rep.state["worker"]
    assert st["merged"] == direct_aggregate(keys, op)
    assert st["windows"] == len(range(0, keys.shape[0], 500))
    assert st["migration_bytes"] > 0


def test_history_reads_rows_as_a_concatenation_would():
    """The session's per-feed history reads rows at absolute indices, across
    and inside its parts, and keeps the running maximum of all values."""
    from repro.topology.engine import _History

    rng = np.random.default_rng(3)
    parts = [rng.random(n) for n in (5, 0, 1, 7, 3)]
    h = _History()
    for p in parts:
        h.append(p)
    whole = np.concatenate(parts)
    idx = np.array([15, 0, 4, 5, 6, 12, 13, 5], dtype=np.int64)
    assert len(h) == whole.shape[0]
    np.testing.assert_array_equal(h[idx], whole[idx])
    assert h[idx].dtype == whole.dtype
    assert h.max == whole.max()
    roots = _History()
    roots.append(np.arange(4, dtype=np.int64))
    assert roots[np.array([], dtype=np.int64)].dtype == np.int64
