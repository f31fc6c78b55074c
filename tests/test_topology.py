"""Topology API (ISSUE 3): typed configs, multi-stage engines vs the
per-tuple reference oracle, scoped events.

Equivalence contract (extends DESIGN.md §6 to multi-hop):

* SG / FG / PKG — the batched multi-stage engine matches the per-tuple
  reference interpreter *exactly* (same routing, hence identical per-edge
  metrics up to float noise), even through a fanout transform.
* DC / WC / FISH — bounded drift: sub-chunked frequencies shift individual
  assignments but every per-edge paper metric stays within tight bands.
"""

import numpy as np
import pytest

from repro.core import (DChoices, FieldGrouping, FishGrouper,
                        MembershipEvent, PartialKeyGrouping, ShuffleGrouping,
                        WChoices)
from repro.data.synthetic import zipf_time_evolving
from repro.topology import (SCHEME_CONFIGS, DChoicesConfig, Edge, FishConfig,
                            ScopedEvent, ServingTopologyEngine, ShuffleConfig,
                            SimulatorEngine, Source, Stage, Topology,
                            build_grouper, config_for, hashed_fanout,
                            project_mod)

from repro.analysis.contracts import (DRIFT_SCHEMES, EXACT_SCHEMES,
                                      SCHEMES)


@pytest.fixture(scope="module")
def keys():
    return zipf_time_evolving(6_000, num_keys=600, z=1.4, seed=0)


def _word_count(spec, split_w=5, count_w=7, fanout=3, vocab=300):
    return Topology(
        name="wc",
        stages=(Stage("split", split_w,
                      transform=hashed_fanout(fanout, vocab)),
                Stage("count", count_w)),
        edges=(Edge("source", "split", ShuffleConfig()),
               Edge("split", "count", spec)),
    )


# ---------------------------------------------------------------------------
# typed configs round-trip + validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", SCHEMES)
def test_config_build_names_its_scheme(scheme):
    cfg = config_for(scheme)
    g = cfg.build(8)
    assert type(g) is {
        "sg": ShuffleGrouping, "fg": FieldGrouping, "pkg": PartialKeyGrouping,
        "dc": DChoices, "wc": WChoices, "fish": FishGrouper}[scheme]
    assert cfg.scheme == scheme == g.name
    g2 = build_grouper(scheme, 8)
    for k in range(200):
        assert g.probe_route(k) == g2.probe_route(k), k


def test_config_validation_rejects_bad_fields():
    with pytest.raises(ValueError):
        FishConfig(alpha=1.5)
    with pytest.raises(ValueError):
        FishConfig(epoch=0)
    with pytest.raises(ValueError):
        FishConfig(theta_frac=-0.25)
    with pytest.raises(ValueError):
        DChoicesConfig(k_max=0)
    with pytest.raises(ValueError):
        config_for("nope")
    with pytest.raises(ValueError):
        ShuffleConfig().build(0)
    # paper Fig. 13 sweeps theta up to 2/n — must be representable
    assert FishConfig(theta_frac=2.0).to_params().theta(8) == 0.25


def test_configs_are_reusable_values():
    cfg = FishConfig(epoch=100)
    g1, g2 = cfg.build(4), cfg.build(4)
    assert g1 is not g2
    g1.assign_batch(np.arange(50, dtype=np.int64))
    assert g2.memory_overhead() == 0  # builds never share state
    assert cfg == FishConfig(epoch=100)  # frozen value semantics
    assert hash(cfg) == hash(FishConfig(epoch=100))


# ---------------------------------------------------------------------------
# topology validation
# ---------------------------------------------------------------------------


def test_topology_validation():
    s = Stage("a", 2)
    with pytest.raises(ValueError):  # unknown dst
        Topology("t", stages=(s,), edges=(
            Edge("source", "b", ShuffleConfig()),))
    with pytest.raises(ValueError):  # unreachable stage
        Topology("t", stages=(s, Stage("b", 2)), edges=(
            Edge("source", "a", ShuffleConfig()),))
    with pytest.raises(ValueError):  # fan-in onto one pool
        Topology("t", stages=(s, Stage("b", 2)), edges=(
            Edge("source", "a", ShuffleConfig()),
            Edge("source", "b", ShuffleConfig()),
            Edge("a", "b", ShuffleConfig())))
    with pytest.raises(TypeError):  # stringly-typed grouping rejected
        Edge("source", "a", "fish")
    with pytest.raises(ValueError):  # reserved name
        Stage("source", 2)
    # a valid 3-stage chain orders edges source-out first
    topo = Topology("t3", stages=(
        Stage("a", 2, transform=project_mod(10)), Stage("b", 2),
        Stage("c", 2)), edges=(
        Edge("b", "c", ShuffleConfig()),
        Edge("a", "b", ShuffleConfig()),
        Edge("source", "a", ShuffleConfig())))
    assert [e.name for e in topo.ordered_edges()] == [
        "source->a", "a->b", "b->c"]
    assert topo.sinks() == ["c"]
    assert topo.fanout_to("a") == 1 and topo.fanout_to("b") == 1


def test_transforms_are_deterministic_and_shaped():
    t = hashed_fanout(4, 100)
    keys = np.array([3, 3, 17], dtype=np.int64)
    out1, out2 = t(keys), t(keys)
    np.testing.assert_array_equal(out1, out2)
    assert out1.shape == (12,)
    # same key always emits the same word set — hot key ⇒ hot words
    np.testing.assert_array_equal(out1[:4], out1[4:8])
    assert (out1 >= 0).all() and (out1 < 100).all()
    p = project_mod(8)
    np.testing.assert_array_equal(p(np.array([7, 8, 9])), [7, 0, 1])


# ---------------------------------------------------------------------------
# multi-stage engine vs the per-tuple reference oracle
# ---------------------------------------------------------------------------


def _reports(scheme, keys, **topo_kw):
    topo = _word_count(config_for(scheme), **topo_kw)
    src = Source(keys, arrival_rate=2e4)
    rb = SimulatorEngine(mode="batched").run(topo, src)
    rr = SimulatorEngine(mode="reference").run(topo, src)
    return rb, rr


@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
def test_multistage_exact_vs_oracle(scheme, keys):
    rb, rr = _reports(scheme, keys)
    for eb, er in zip(rb.edges, rr.edges):
        assert eb.edge == er.edge
        assert eb.memory_overhead == er.memory_overhead, eb.edge
        for field, v_ref in er.row().items():
            assert eb.row()[field] == pytest.approx(v_ref, rel=1e-9), \
                (eb.edge, field)
    assert rb.e2e_latency_p99 == pytest.approx(rr.e2e_latency_p99, rel=1e-9)
    assert rb.total_time == pytest.approx(rr.total_time, rel=1e-9)


@pytest.mark.parametrize("scheme", DRIFT_SCHEMES)
def test_multistage_drift_bounded_vs_oracle(scheme, keys):
    rb, rr = _reports(scheme, keys)
    for eb, er in zip(rb.edges, rr.edges):
        assert eb.execution_time == pytest.approx(er.execution_time,
                                                  rel=0.05), eb.edge
        assert eb.throughput == pytest.approx(er.throughput, rel=0.05)
        assert eb.memory_overhead == pytest.approx(er.memory_overhead,
                                                   rel=0.25)
        # load balance must not degrade materially vs the oracle
        assert eb.imbalance <= er.imbalance + 0.05, eb.edge
        # queueing latency stays the same order of magnitude
        assert eb.latency_p99 <= max(er.latency_p99 * 10.0, 0.05)
    assert rb.total_time == pytest.approx(rr.total_time, rel=0.05)


def test_downstream_arrivals_are_upstream_finishes(keys):
    """Chaining sanity: the counting edge cannot start before the split
    finishes — e2e p99 is at least each edge's own p99."""
    rb, _ = _reports("sg", keys)
    assert rb.e2e_latency_p99 >= max(e.latency_p99 for e in rb.edges)
    n_split = rb.edge("split").n_tuples
    assert rb.edge("count").n_tuples == n_split * 3  # fanout


# ---------------------------------------------------------------------------
# one engine protocol: the same Topology through both engines
# ---------------------------------------------------------------------------


def test_wordcount_same_topology_both_engines(keys):
    topo = _word_count(FishConfig())
    src = Source(keys, arrival_rate=2e4)
    r_sim = SimulatorEngine().run(topo, src)
    r_srv = ServingTopologyEngine(max_requests=64).run(topo, src)
    for rep in (r_sim, r_srv):
        assert [e.edge for e in rep.edges] == ["source->split",
                                               "split->count"]
        assert [e.scheme for e in rep.edges] == ["sg", "fish"]
        assert rep.edge("count").latency_p99 > 0
        assert rep.edge("count").memory_overhead > 0
        assert rep.e2e_latency_p99 > 0
    assert r_sim.engine == "dspe-batched"
    assert r_srv.engine == "serving"
    # serving subsampled the source but dropped nothing
    assert r_srv.n_source_tuples == 64
    assert sum(e.dropped for e in r_srv.edges) == 0
    assert r_srv.edge("count").n_tuples == 64 * 3


# ---------------------------------------------------------------------------
# scoped events: per-stage membership churn with remap accounting
# ---------------------------------------------------------------------------


def test_scoped_membership_event_remaps_one_edge(keys):
    topo = _word_count(config_for("fg"))
    n_count = keys.shape[0] * 3
    events = [ScopedEvent("count",
                          MembershipEvent(at=n_count // 2,
                                          workers=tuple(range(6))))]
    rep = SimulatorEngine().run(topo, Source(keys, arrival_rate=2e4),
                                events)
    er = rep.edge("count")
    assert len(er.remap_events) == 1
    # consistent hashing: removing 1 of 7 workers moves a bounded slice
    assert er.remap_frac_mean is not None
    assert 0.0 < er.remap_frac_mean < 0.5
    # the split edge saw no event
    assert rep.edge("split").remap_events == []
    # SG has no key affinity: remap fraction is None
    rep_sg = SimulatorEngine().run(
        _word_count(config_for("sg")), Source(keys, arrival_rate=2e4),
        events)
    assert rep_sg.edge("count").remap_frac_mean is None
    assert rep_sg.edge("count").remap_events[0]["moved"] is None


def test_serving_engine_scoped_events(keys):
    topo = _word_count(config_for("fg"), fanout=2)
    n_count = 48 * 2
    events = [
        # worker 6 fails mid-stream…
        ScopedEvent("count", MembershipEvent(at=n_count // 3,
                                             workers=tuple(range(6)))),
        # …then the pool scales out with a fresh id (ids are never reused)
        ScopedEvent("count", MembershipEvent(at=2 * n_count // 3,
                                             workers=tuple(range(6)) + (7,))),
    ]
    eng = ServingTopologyEngine(max_requests=48)
    rep = eng.run(topo, Source(keys, arrival_rate=2e4), events)
    er = rep.edge("count")
    assert sum(e.dropped for e in rep.edges) == 0
    assert len(er.remap_events) == 2
    assert er.remap_frac_mean is not None and er.remap_frac_mean < 0.6


def test_report_roundtrips_to_dict(keys):
    rep = SimulatorEngine().run(_word_count(config_for("pkg")),
                                Source(keys, arrival_rate=2e4))
    d = rep.to_dict()
    assert d["engine"] == "dspe-batched"
    assert len(d["edges"]) == 2
    for e in d["edges"]:
        for f in ("latency_p50", "latency_p99", "memory_overhead",
                  "imbalance", "scheme", "workers"):
            assert f in e
    with pytest.raises(KeyError):
        rep.edge("nope")
