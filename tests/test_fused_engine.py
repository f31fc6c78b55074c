"""Fused device feed path (ISSUE 6): one jitted launch per (edge, segment).

The equivalence contract, against the host engines:

* **integer-exact SG/FG/PKG** — routing counts, replica sets
  (``memory_overhead``), imbalance and merged windows match the batched
  engine bit-for-bit across feeds and events; finish times / latencies
  agree up to the f32 timing epsilon (the device FIFO runs in float32, so
  a hot worker's sequential busy-time accumulation drifts by a few
  hundred ulps — DESIGN.md §11).
* **§6-banded DC/WC/FISH** — the fused tracker is a dense device table
  (no SpaceSaving eviction), so routing drifts within the DESIGN.md §6
  bands against the reference oracle, while window contents stay exact.
* **merged windows exact for every scheme** — keyed window state is
  routed-stream-identical no matter which engine routed it, so the
  merged windows equal :func:`direct_aggregate` on the raw stream.
* **one dispatch per steady-state feed** — when feed boundaries land on
  pane boundaries and no events fire, each ``session.feed`` costs one
  device launch; events and mid-feed pane cuts add segments.
* **pow2-padded shapes** — feeds in the same padding bucket reuse the
  jitted segment function (no recompilation).
"""

import warnings

import numpy as np
import pytest

from repro.core import CapacityEvent, MembershipEvent
from repro.core import stream
from repro.core.stream import simulate_edge
from repro.data.synthetic import zipf_time_evolving
from repro.kernels import feed_fused
from repro.obs import Telemetry
from repro.state import KeyedStateManager, WindowOp, direct_aggregate
from repro.topology import (Edge, ScopedEvent, ServingTopologyEngine,
                            SimulatorEngine, Source, Stage, Topology,
                            WindowOp as TopoWindowOp, config_for)

from repro.analysis.contracts import (DRIFT_SCHEMES, EXACT_SCHEMES,
                                      SCHEMES)

# float32 device FIFO: sequential busy-time accumulation on a hot worker
# drifts a few hundred ulps from the float64 host scan (DESIGN.md §11)
F32_REL = 1e-4


@pytest.fixture(scope="module")
def keys():
    return zipf_time_evolving(6_000, num_keys=600, z=1.4, seed=0)


@pytest.fixture(scope="module")
def values(keys):
    return np.random.default_rng(5).integers(1, 10, keys.shape[0]).astype(
        np.int64)


def _topo(scheme, op=None, workers=8):
    return Topology(
        name=f"fused-{scheme}",
        stages=(Stage("agg", workers, operator=op),),
        edges=(Edge("source", "agg", config_for(scheme)),),
    )


def _run(mode, topo, src, events=(), feeds=1):
    sess = SimulatorEngine(mode=mode).open(
        topo, arrival_rate=src.arrival_rate)
    if events:
        if events:
            sess.advance(events)
    n = int(src.keys.shape[0])
    for batch in src.iter_batches(batch_size=-(-n // feeds)):
        sess.feed(batch)
    return sess.close()


# ---------------------------------------------------------------------------
# fused vs batched: integer-exact for the sequential schemes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
@pytest.mark.parametrize("feeds", (1, 4))
def test_fused_exact_schemes_match_batched(scheme, feeds, keys, values):
    op = TopoWindowOp(agg="sum", value="payload", size=1_500)
    topo = _topo(scheme, op)
    src = Source(keys, arrival_rate=2e4, values=values)
    rb = _run("batched", topo, src, feeds=feeds)
    rf = _run("fused", topo, src, feeds=feeds)
    eb, ef = rb.edges[0], rf.edges[0]
    assert ef.n_tuples == eb.n_tuples
    assert ef.memory_overhead == eb.memory_overhead
    assert ef.imbalance == eb.imbalance
    assert ef.latency_p99 == pytest.approx(eb.latency_p99, rel=F32_REL)
    assert ef.latency_avg == pytest.approx(eb.latency_avg, rel=F32_REL)
    assert ef.execution_time == pytest.approx(eb.execution_time, rel=F32_REL)
    assert rf.state["agg"]["merged"] == rb.state["agg"]["merged"]
    assert rf.state["agg"]["partials"] == rb.state["agg"]["partials"]


# ---------------------------------------------------------------------------
# fused vs the reference oracle: §6 bands for the epoch-paced schemes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", DRIFT_SCHEMES)
def test_fused_drift_schemes_within_bands(scheme, keys, values):
    op = TopoWindowOp(agg="sum", value="payload", size=1_500)
    topo = _topo(scheme, op)
    src = Source(keys, arrival_rate=2e4, values=values)
    ro = _run("reference", topo, src)
    rf = _run("fused", topo, src, feeds=3)
    eo, ef = ro.edges[0], rf.edges[0]
    assert ef.n_tuples == eo.n_tuples
    assert ef.execution_time == pytest.approx(eo.execution_time, rel=0.05)
    assert ef.throughput == pytest.approx(eo.throughput, rel=0.05)
    assert ef.memory_overhead == pytest.approx(eo.memory_overhead, rel=0.25)
    assert ef.imbalance <= eo.imbalance + 0.05
    assert ef.latency_p99 <= max(eo.latency_p99 * 10.0, 0.05)
    # window contents are routing-independent: exact under drift too
    assert rf.state["agg"]["merged"] == direct_aggregate(
        keys, op, values=values)


def test_fused_fish_hot_set_change_inside_a_segment():
    """The ZF hot-set flip lands inside an 8,192-tuple segment: each tuple
    reads its own epoch's frequencies, so the keys that were hot before
    the flip keep their spread for their pre-flip tuples instead of
    falling back to two choices."""
    ks = zipf_time_evolving(24_576, num_keys=2_000, z=1.2, flip_head=2_000,
                            seed=0)
    src = Source(ks, arrival_rate=1e5)
    topo = _topo("fish", workers=32)
    rb = _run("batched", topo, src, feeds=3)
    rf = _run("fused", topo, src, feeds=3)
    eb, ef = rb.edges[0], rf.edges[0]
    assert ef.dispatches == 3
    assert ef.imbalance <= eb.imbalance + 0.05
    assert ef.execution_time == pytest.approx(eb.execution_time, rel=0.05)
    assert ef.memory_overhead == pytest.approx(eb.memory_overhead, rel=0.25)


# ---------------------------------------------------------------------------
# multi-feed with events + payload windows: the full churn protocol
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", SCHEMES)
def test_fused_multi_feed_with_events(scheme, keys, values):
    op = TopoWindowOp(agg="sum", value="payload", size=1_024,
                      backend="dict")
    topo = _topo(scheme, op)
    src = Source(keys, arrival_rate=2e4, values=values)
    events = [
        ScopedEvent("agg", MembershipEvent(at=2_000,
                                           workers=tuple(range(10)))),
        ScopedEvent("agg", CapacityEvent(at=3_500,
                                         capacities={0: 4e-3})),
        ScopedEvent("agg", MembershipEvent(at=5_000,
                                           workers=tuple(range(1, 10)))),
    ]
    rb = _run("batched", topo, src, events, feeds=4)
    rf = _run("fused", topo, src, events, feeds=4)
    # keyed window state is exact regardless of scheme: same routed stream
    assert rf.state["agg"]["merged"] == rb.state["agg"]["merged"]
    assert rf.state["agg"]["merged"] == direct_aggregate(
        keys, op, values=values)
    ef = rf.edges[0]
    assert len(ef.remap_events) == len(rb.edges[0].remap_events) == 2
    if scheme in EXACT_SCHEMES:
        eb = rb.edges[0]
        assert ef.memory_overhead == eb.memory_overhead
        assert ef.latency_p99 == pytest.approx(eb.latency_p99, rel=F32_REL)
        assert rf.state["agg"]["migration_bytes"] == \
            rb.state["agg"]["migration_bytes"]


# ---------------------------------------------------------------------------
# replica sets: kept on the host from the workers every launch returns
# ---------------------------------------------------------------------------


def _two_stage(scheme):
    """A windowed ``count`` stage and a ``merge`` stage with no operator,
    both edges grouped by ``scheme``: the merge edge has no pane."""
    op = TopoWindowOp(agg="sum", value="payload", size=1_024)
    return Topology(name="r", stages=(
        Stage("count", 8, operator=op), Stage("merge", 4)),
        edges=(Edge("source", "count", config_for(scheme)),
               Edge("count", "merge", config_for(scheme))))


def _replicas(grouper):
    return {k: set(ws) for k, ws in grouper.replicas.items()}


def _session_replicas(mode, topo, src, events, monkeypatch):
    """Each edge's ``grouper.replicas`` at close, and the reshaped
    grouper's at every membership event, as the event handler sees it."""
    at_events = []
    real = stream._apply_events

    def spy(i, mem_ev, ev_idx, cap_ev, cap_idx, grouper, *rest):
        if ev_idx < len(mem_ev) and mem_ev[ev_idx].at == i:
            at_events.append(_replicas(grouper))
        return real(i, mem_ev, ev_idx, cap_ev, cap_idx, grouper, *rest)

    with monkeypatch.context() as mp:
        mp.setattr(stream, "_apply_events", spy)
        sess = SimulatorEngine(mode=mode).open(
            topo, arrival_rate=src.arrival_rate)
        if events:
            sess.advance(events)
        for batch in src.iter_batches(batch_size=1_500):
            sess.feed(batch)
        rep = sess.close()
    at_close = {name: _replicas(st.grouper)
                for name, st in sess._st.items()}
    return rep, at_close, at_events


def _report_replicas(mode, scheme, keys):
    """``grouper.replicas`` after each of two ``simulate_edge`` calls on
    one carried state, each ending 476 or 952 tuples into an open pane:
    the fused edge syncs for its metrics with the pane unflushed."""
    g = config_for(scheme).build(8)
    sink = (KeyedStateManager(WindowOp(agg="count", size=1_024))
            if mode == "fused" else None)
    caps = np.full(8, 4e-4)
    state, out = None, []
    for lo, hi in ((0, 1_500), (1_500, 3_000)):
        ts = np.arange(lo, hi, dtype=np.float64) / 2e4
        res = simulate_edge(g, keys[lo:hi], times=ts, arrival_rate=2e4,
                            mode=mode, capacities=caps, state=state,
                            state_sink=sink)
        state = res.state
        out.append((_replicas(g), res.metrics.memory_overhead))
    return out


@pytest.mark.parametrize("sync", ("close", "event", "merge_edge",
                                  "report"))
@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
def test_fused_replica_sets_equal_batched(scheme, sync, keys, values,
                                          monkeypatch):
    # the fused engine records its replica pairs on the host; each way a
    # sync is reached must leave grouper.replicas exactly the batched
    # engine's dict of sets
    if sync == "report":
        assert (_report_replicas("fused", scheme, keys)
                == _report_replicas("batched", scheme, keys))
        return
    src = Source(keys, arrival_rate=2e4, values=values)
    if sync == "merge_edge":
        topo, events = _two_stage(scheme), ()
    else:
        op = TopoWindowOp(agg="sum", value="payload", size=1_024)
        topo = _topo(scheme, op)
        # both events fall inside a feed and inside a pane
        events = () if sync == "close" else (
            ScopedEvent("agg", MembershipEvent(
                at=2_100, workers=tuple(range(10)))),
            ScopedEvent("agg", MembershipEvent(
                at=4_700, workers=tuple(range(1, 10)))))
    rb, close_b, events_b = _session_replicas("batched", topo, src, events,
                                              monkeypatch)
    rf, close_f, events_f = _session_replicas("fused", topo, src, events,
                                              monkeypatch)
    assert close_f == close_b
    assert events_f == events_b
    assert len(events_f) == len(events)
    for eb, ef in zip(rb.edges, rf.edges):
        assert ef.memory_overhead == eb.memory_overhead > 0


@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
def test_replica_pairs_new_sum_to_memory_overhead(scheme, keys, values):
    # every (key, worker) pair is counted new exactly once, on either edge
    tel = Telemetry(enabled=True)
    sess = SimulatorEngine(mode="fused").open(
        _two_stage(scheme), arrival_rate=2e4, telemetry=tel)
    src = Source(keys, arrival_rate=2e4, values=values)
    for batch in src.iter_batches(batch_size=1_500):
        sess.feed(batch)
    rep = sess.close()
    new = [c.value for c in tel.metrics
           if c.name == "fused.replica_pairs_new"]
    assert len(new) == len(rep.edges) == 2
    assert sum(new) == sum(e.memory_overhead for e in rep.edges)
    assert all(e.memory_overhead > 0 for e in rep.edges)
    folds = [s for s in tel.tracer.spans if s.name == "fused.replica_fold"]
    assert sum(s.args["new"] for s in folds) == sum(new)
    assert all(0 <= s.args["new"] <= s.args["pairs"] for s in folds)


# ---------------------------------------------------------------------------
# incremental operator emission: windows flow downstream per feed
# ---------------------------------------------------------------------------


def _merge_topo(scheme, backend="array", workers=6):
    op = TopoWindowOp(agg="sum", value="payload", size=1_000,
                      backend=backend)
    return Topology(name="m", stages=(
        Stage("count", workers, operator=op), Stage("merge", 4)),
        edges=(Edge("source", "count", config_for(scheme)),
               Edge("count", "merge", config_for("fg"))))


@pytest.mark.parametrize("backend", ("dict", "array"))
def test_fused_merge_stage_matches_batched(backend, keys, values):
    src = Source(keys, arrival_rate=2e4, values=values)
    rb = _run("batched", _merge_topo("fg", backend), src, feeds=4)
    rf = _run("fused", _merge_topo("fg", backend), src, feeds=4)
    assert rf.state["count"]["merged"] == rb.state["count"]["merged"]
    assert rf.edges[1].n_tuples == rb.edges[1].n_tuples
    assert rf.edges[1].latency_p99 == pytest.approx(
        rb.edges[1].latency_p99, rel=F32_REL)


@pytest.mark.parametrize("mode", ("batched", "fused"))
def test_operator_emits_incrementally_per_feed(mode, keys, values):
    """Windows that close during a feed reach the downstream merge edge
    before ``close()`` — the merge edge exists (and has tuples) after the
    first window-crossing feed."""
    src = Source(keys, arrival_rate=2e4, values=values)
    sess = SimulatorEngine(mode=mode).open(_merge_topo("fg"),
                                           arrival_rate=2e4)
    feeds = list(src.iter_batches(batch_size=3_000))
    sess.feed(feeds[0])  # 3 windows of 1000 close inside this feed
    st = sess._st.get("count->merge")
    assert st is not None and st.n > 0
    mid = st.n
    sess.feed(feeds[1])
    rep = sess.close()
    assert rep.edges[1].n_tuples > mid
    assert rep.state["count"]["merged"] == direct_aggregate(
        keys, _merge_topo("fg").stages[0].operator, values=values)


@pytest.mark.parametrize("mode", ("batched", "fused"))
def test_windows_longer_than_feeds_keep_e2e_latency(mode, keys, values):
    """A window partial released in a feed can anchor on a tuple an
    earlier feed brought: its end-to-end latency is measured from that
    tuple's arrival, the same as when every window closes inside one
    feed."""
    src = Source(keys, arrival_rate=2e4, values=values)
    # 64 workers: some see their last tuple of a window feeds before it
    # closes
    topo = _merge_topo("fg", workers=64)
    whole = _run(mode, topo, src, feeds=6)  # feed == window
    split = _run(mode, topo, src, feeds=60)
    assert split.e2e_latency_avg == pytest.approx(whole.e2e_latency_avg,
                                                  rel=F32_REL)
    assert split.e2e_latency_p99 == pytest.approx(whole.e2e_latency_p99,
                                                  rel=F32_REL)
    assert split.e2e_latency_avg > 0.0


def test_serving_operator_emits_incrementally(keys, values):
    src = Source(keys[:600], arrival_rate=2e4, values=values[:600])
    eng = ServingTopologyEngine(max_requests=200)
    topo = Topology(name="m", stages=(
        Stage("count", 6, operator=TopoWindowOp(agg="count", size=150)),
        Stage("merge", 4)),
        edges=(Edge("source", "count", config_for("fg")),
               Edge("count", "merge", config_for("fg"))))
    sess = eng.open(topo)
    feeds = list(src.iter_batches(batch_size=200))
    sess.feed(feeds[0])
    st = sess._st.get("count->merge")
    assert st is not None and st.n > 0  # window 0 flowed mid-session
    for b in feeds[1:]:
        sess.feed(b)
    rep = sess.close()
    assert rep.edges[1].n_tuples == rep.state["count"]["partial_entries"]


# ---------------------------------------------------------------------------
# dispatch accounting: one launch per steady-state feed
# ---------------------------------------------------------------------------


def test_one_dispatch_per_steady_state_feed(keys, values):
    # feed size == pane stride: every feed is exactly one event-free
    # segment, so the whole feed is a single device launch
    op = TopoWindowOp(agg="sum", value="payload", size=1_500)
    src = Source(keys, arrival_rate=2e4, values=values)
    rep = _run("fused", _topo("fg", op), src, feeds=4)
    assert rep.edges[0].dispatches == 4
    # without an operator there are no pane cuts either
    rep = _run("fused", _topo("fg"), src, feeds=4)
    assert rep.edges[0].dispatches == 4


def test_events_and_pane_cuts_add_dispatches(keys, values):
    op = TopoWindowOp(agg="sum", value="payload", size=1_024)
    src = Source(keys, arrival_rate=2e4, values=values)
    ev = [ScopedEvent("agg", MembershipEvent(at=2_100,
                                             workers=tuple(range(10))))]
    rep = _run("fused", _topo("fg", op), src, ev, feeds=2)
    # 2 feeds of 3000: pane cuts at 1024/2048 + the event cut at 2100 make
    # feed 1 four segments; cuts at 3072/4096/5120 make feed 2 four more
    assert rep.edges[0].dispatches == 8
    # host engines never dispatch
    assert _run("batched", _topo("fg", op), src,
                ev, feeds=2).edges[0].dispatches == 0


def test_dispatches_surface_on_edge_result(keys):
    g = config_for("fg").build(8)
    res = simulate_edge(g, keys[:1_000], arrival_rate=2e4, mode="fused",
                        capacities=np.full(8, 4e-4))
    assert res.dispatches == 1
    g2 = config_for("fg").build(8)
    res2 = simulate_edge(g2, keys[:1_000], arrival_rate=2e4,
                         capacities=np.full(8, 4e-4))
    assert res2.dispatches == 0
    np.testing.assert_allclose(res.finishes, res2.finishes, rtol=F32_REL)


# ---------------------------------------------------------------------------
# pow2 padding: same bucket → no recompilation
# ---------------------------------------------------------------------------


def test_same_bucket_feeds_do_not_retrace(keys, values):
    src = Source(keys, arrival_rate=2e4, values=values)
    op = TopoWindowOp(agg="sum", value="payload", size=3_000)
    sess = SimulatorEngine(mode="fused").open(_topo("fg", op),
                                              arrival_rate=2e4)
    feeds = list(src.iter_batches(batch_size=1_500))
    sess.feed(feeds[0])
    sess.feed(feeds[1])  # shapes warmed: every pad bucket seen
    before = feed_fused.TRACE_COUNT
    sess.feed(feeds[2])
    sess.feed(feeds[3])
    assert feed_fused.TRACE_COUNT == before  # same (1500→2048) bucket
    sess.close()


# ---------------------------------------------------------------------------
# the TPU FIFO form: associative scan == sequential scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lanes", (5, 33, 129))
@pytest.mark.parametrize("n_pad,m", ((64, 50), (4_096, 4_000),
                                     (16_384, 16_384)))
def test_fifo_assoc_matches_scan(n_pad, m, lanes):
    """``_fifo_assoc`` (the default on TPU) gives the finish times and
    final busy of ``_fifo_scan``: padding tuples sink into the phantom
    lane at t=0 as ``run_segment`` pads them, and worker 0 gets exactly
    one tuple."""
    import jax.numpy as jnp

    rng = np.random.default_rng(n_pad + lanes)
    phantom = lanes - 1
    workers = np.full(n_pad, phantom, np.int32)
    workers[:m] = rng.integers(1, max(phantom, 2), m)
    workers[m // 2] = 0
    t = np.zeros(n_pad, np.float32)
    t[:m] = np.sort(rng.uniform(0.0, m / 2e4, m)).astype(np.float32)
    busy = rng.uniform(0.0, 0.05, lanes).astype(np.float32)
    busy[phantom] = 0.0
    caps = rng.uniform(2e-5, 4e-3, lanes).astype(np.float32)
    caps[phantom] = 1.0
    args = tuple(jnp.asarray(x) for x in (busy, caps, workers, t))
    b_scan, f_scan = feed_fused._fifo_scan(*args)
    b_assoc, f_assoc = feed_fused._fifo_assoc(*args)
    np.testing.assert_allclose(np.asarray(f_assoc), np.asarray(f_scan),
                               rtol=F32_REL)
    np.testing.assert_allclose(np.asarray(b_assoc), np.asarray(b_scan),
                               rtol=F32_REL)


def test_bucket_boundaries_are_pow2():
    assert feed_fused._bucket(1) == feed_fused.MIN_BUCKET
    assert feed_fused._bucket(64) == 64
    assert feed_fused._bucket(65) == 128
    assert feed_fused._bucket(1_500) == 2_048
    assert feed_fused._bucket(2_048) == 2_048


# ---------------------------------------------------------------------------
# fallback: unsupported inputs delegate to the host engines, warning once
# ---------------------------------------------------------------------------


def test_fused_falls_back_on_negative_keys():
    ks = np.array([-3, 1, 2, -1] * 50, dtype=np.int64)
    g = config_for("fg").build(4)
    with pytest.warns(UserWarning, match="falling back"):
        res = simulate_edge(g, ks, arrival_rate=1e4, mode="fused",
                            capacities=np.full(4, 3e-4))
    g2 = config_for("fg").build(4)
    ref = simulate_edge(g2, ks, arrival_rate=1e4,
                        capacities=np.full(4, 3e-4))
    np.testing.assert_array_equal(res.finishes, ref.finishes)
    # the sentinel sticks: the next feed delegates silently
    n = ks.shape[0]
    ts = (np.arange(n, 2 * n, dtype=np.float64)) / 1e4
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        res2 = simulate_edge(g, ks, times=ts, arrival_rate=1e4,
                             mode="fused", state=res.state)
    assert res2.dispatches == 0


def test_fused_rejects_state_sink_in_host_modes(keys):
    g = config_for("fg").build(4)
    mgr = KeyedStateManager(WindowOp(agg="count", size=100))
    with pytest.raises(ValueError, match="state_sink"):
        simulate_edge(g, keys[:100], arrival_rate=1e4, mode="batched",
                      state_sink=mgr)


# ---------------------------------------------------------------------------
# fused rejection predicate
# ---------------------------------------------------------------------------


def test_fused_reject_reasons(keys):
    g = config_for("fg").build(4)
    ok = feed_fused.fused_reject_reason(g, keys[:100], None, None, None)
    assert ok is None
    bad = feed_fused.fused_reject_reason(
        g, np.array([-1, 2]), None, None, None)
    assert bad is not None and "negative" in bad
    obs = feed_fused.fused_reject_reason(
        g, keys[:100], None, None, lambda *a: None)
    assert obs is not None


# ---------------------------------------------------------------------------
# trace/transfer auditor (ISSUE 7): the §11 budgets hold on a live runner
# ---------------------------------------------------------------------------


from repro.analysis.audit import EdgeAuditor, TraceBudget  # noqa: E402
from repro.topology import RecordBatch  # noqa: E402


def _mixed_batches(keys, values, sizes):
    """Slices of the key stream at the given (uneven) batch sizes, with a
    shared monotone clock.  The first record carries the global max key so
    the runner's key-capacity axis is fixed from the warm-up feed on —
    leaving the pow2 pad bucket as the only shape axis under audit."""
    total = sum(sizes)
    ks = np.resize(keys, total).copy()
    ks[0] = ks.max()
    vs = np.resize(values, total)
    ts = np.arange(total, dtype=np.float64) / 2e4
    out, lo = [], 0
    for n in sizes:
        out.append(RecordBatch(keys=ks[lo:lo + n],
                               timestamps=ts[lo:lo + n],
                               values=vs[lo:lo + n]))
        lo += n
    return out


def test_auditor_retrace_budget_mixed_batch_sizes(keys, values):
    # feeds spanning three pow2 pad buckets: 900/700→1024, 1500/2000→2048,
    # 64→64.  TRACE_COUNT must stay within the documented signature set —
    # one trace per distinct bucket at most, zero for repeats.
    sizes = (900, 1_500, 64, 700, 1_500, 900, 2_000, 64)
    batches = _mixed_batches(keys, values, sizes)
    sess = SimulatorEngine(mode="fused").open(_topo("pkg"),
                                              arrival_rate=2e4)
    sess.feed(batches[0])  # warm-up: creates the runner, pins kcap
    runner = sess._st["source->agg"].state.device
    assert runner is not None
    with TraceBudget(3, what="mixed-bucket sweep"):
        with EdgeAuditor(runner) as aud:
            for b in batches[1:]:
                sess.feed(b)
    aud.assert_retrace_budget()
    # every launch dispatched under a documented signature: the pad-bucket
    # axis takes exactly the three pow2 values, nothing else varies
    assert {sig[1] for sig in aud.signatures} == {64, 1_024, 2_048}
    assert aud.dispatches == len(sizes) - 1  # no panes, no events
    assert all(e.tuples == n
               for e, n in zip((e for e in aud.events
                                if e.kind == "segment"), sizes[1:]))
    sess.close()


def test_auditor_sync_budget_pane_boundaries(keys, values):
    # device→host transfers only at pane flushes and close (HOST_SYNC_POINTS):
    # feed size == pane stride, so every flush lands on the pane grid and
    # the close-time drain is the only off-grid sync
    op = TopoWindowOp(agg="sum", value="payload", size=1_500)
    src = Source(keys, arrival_rate=2e4, values=values)
    sess = SimulatorEngine(mode="fused").open(_topo("fg", op),
                                              arrival_rate=2e4)
    feeds = list(src.iter_batches(batch_size=1_500))
    sess.feed(feeds[0])  # warm-up: creates the runner
    runner = sess._st["source->agg"].state.device
    with EdgeAuditor(runner, pane_stride=1_500) as aud:
        for b in feeds[1:]:
            sess.feed(b)
        aud.assert_retrace_budget()
        with aud.expect("close"):
            rep = sess.close()
    aud.assert_sync_budget(closed=True)
    assert aud.dispatches == len(feeds) - 1
    assert rep.edges[0].n_tuples == keys.shape[0]
    # the audited feeds flushed their panes on the stride grid
    flushes = [e for e in aud.events
               if e.kind == "flush_pane" and e.context == "feed"]
    assert flushes and all(e.offset % 1_500 == 0 for e in flushes)
