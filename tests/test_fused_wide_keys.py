"""Fused sessions over key ids past 2^21, on the device path.

The open pane is keyed by slot, so its table follows the pane's tuples,
not the key space; the per-key tables (tracker, candidate memory, replica
matrix, hash cache) grow to the key capacity, up to ``KEY_CAP_LIMIT``.
A few thousand distinct ids are spread over [0, 10^7) (a 2^24-row key
capacity, the paper's ZF key space) or lie just under 2^22 (a 2^22-row
capacity, small tables).  Every session here must stay on the fused path
(no fallback warning, launches on both edges), and its results must match
the repo's plain engines: exactly for SG, FG and PKG against the batched
engine, within the DESIGN.md §6 bands for FISH against the per-tuple
reference; merged windows equal ``direct_aggregate`` for every scheme.
"""

from __future__ import annotations

import warnings

import numpy as np
import pytest

from repro.data.synthetic import zipf_time_evolving
from repro.kernels import feed_fused
from repro.obs import Telemetry
from repro.state import direct_aggregate
from repro.topology import (Edge, SimulatorEngine, Source, Stage, Topology,
                            WindowOp, config_for)

RATE = 2e4
WINDOW = 2_048
FEED = 1_024
WORKERS = 8
DISTINCT = 3_000
F32_REL = 1e-4  # the device FIFO's float32 drift (DESIGN.md §11)
SPACES = {"to_1e7": 10 ** 7, "near_2p22": 1 << 22}


def _ids(space):
    """DISTINCT ids in random order: over [0, 10^7) with 10^7 - 1 among
    them, or among the 2^20 ids just under 2^22 with 2^22 - 1 among them."""
    rng = np.random.default_rng(17)
    top = SPACES[space]
    lo = 0 if space == "to_1e7" else top - (1 << 20)
    ids = lo + rng.choice(top - 1 - lo, DISTINCT - 1, replace=False)
    ids = np.append(ids, top - 1)
    assert ids.min() >= 0 and ids.max() == top - 1 and ids.max() >= 1 << 21
    return rng.permutation(ids).astype(np.int64)


def _stream(space, n=4 * WINDOW + FEED // 2):
    """Zipf ranks over DISTINCT keys (a hot head flipped at 0.8), mapped to
    wide ids, and payloads 1..9."""
    ranks = zipf_time_evolving(n, num_keys=DISTINCT, z=1.2, flip_head=300,
                               seed=7)
    keys = _ids(space)[ranks]
    values = (np.arange(n) % 9 + 1).astype(np.float64)
    return keys, values


def _topo(scheme):
    op = WindowOp(agg="sum", value="payload", size=WINDOW)
    return Topology(
        name=f"wide-{scheme}",
        stages=(Stage("count", WORKERS, operator=op), Stage("merge", 4)),
        edges=(Edge("source", "count", config_for(scheme)),
               Edge("count", "merge", config_for("fg"))))


def _run(mode, scheme, keys, values, telemetry=None):
    sess = SimulatorEngine(mode=mode).open(
        _topo(scheme), arrival_rate=RATE, telemetry=telemetry)
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="simulate_edge falling back",
                                category=UserWarning)
        for b in Source(keys, arrival_rate=RATE,
                        values=values).iter_batches(batch_size=FEED):
            sess.feed(b)
        rep = sess.close()
    return sess, rep


def _fused_run(scheme, keys, values):
    sess, rep = _run("fused", scheme, keys, values)
    count = sess._st["source->count"].state.device
    assert isinstance(count, feed_fused.FusedEdgeRunner)
    # the count edge's key capacity covers the widest id; its pane does not
    assert count._kcap == 1 << int(keys.max()).bit_length()
    assert count._slots == WINDOW
    for e in rep.edges:
        assert e.dispatches > 0, e.edge
    return rep


@pytest.mark.parametrize("space", sorted(SPACES))
@pytest.mark.parametrize("scheme", ("sg", "fg", "pkg"))
def test_wide_keys_exact_schemes_match_batched(scheme, space):
    keys, values = _stream(space)
    rf = _fused_run(scheme, keys, values)
    _, rb = _run("batched", scheme, keys, values)
    for ef, eb in zip(rf.edges, rb.edges):
        assert ef.n_tuples == eb.n_tuples
        assert ef.memory_overhead == eb.memory_overhead
        assert ef.imbalance == eb.imbalance
        assert ef.latency_p99 == pytest.approx(eb.latency_p99, rel=F32_REL)
    assert rf.state["count"]["partials"] == rb.state["count"]["partials"]
    op = _topo(scheme).stage("count").operator
    assert rf.state["count"]["merged"] == direct_aggregate(keys, op,
                                                           values=values)


@pytest.mark.parametrize("space", sorted(SPACES))
def test_wide_keys_fish_within_bands(space):
    keys, values = _stream(space)
    rf = _fused_run("fish", keys, values)
    _, ro = _run("reference", "fish", keys, values)
    eo, ef = ro.edges[0], rf.edges[0]
    assert ef.n_tuples == eo.n_tuples
    assert ef.execution_time == pytest.approx(eo.execution_time, rel=0.05)
    assert ef.throughput == pytest.approx(eo.throughput, rel=0.05)
    assert ef.memory_overhead == pytest.approx(eo.memory_overhead, rel=0.25)
    assert ef.imbalance <= eo.imbalance + 0.05
    op = _topo("fish").stage("count").operator
    assert rf.state["count"]["merged"] == direct_aggregate(keys, op,
                                                           values=values)


def test_key_ids_past_the_cap_still_fall_back():
    g = config_for("fg").build(4)
    big = np.array([3, feed_fused.KEY_CAP_LIMIT], dtype=np.int64)
    reason = feed_fused.fused_reject_reason(g, big, None, None, None)
    assert reason is not None and "capacity limit" in reason
    assert feed_fused.fused_reject_reason(g, big - 1, None, None,
                                          None) is None


def test_fish_epoch_points_equal_a_host_read_of_the_tracker(monkeypatch):
    """The hot-set size and churn computed on the device equal what a copy
    of the whole tracker to the host gives, point by point."""
    real = feed_fused.FusedEdgeRunner._fish_epoch_points
    prev = {}
    want = []

    def spy(self, grouper, state, lo, hi):
        trk = np.array(self.trk)[:-1]
        total = float(trk.sum())
        theta = grouper.params.theta(grouper.num_workers)
        hot = (set(np.flatnonzero(trk > theta * total).tolist())
               if total > 0.0 else set())
        want.append((len(hot), len(hot ^ prev.get(id(self), set()))))
        prev[id(self)] = hot
        return real(self, grouper, state, lo, hi)

    monkeypatch.setattr(feed_fused.FusedEdgeRunner, "_fish_epoch_points",
                        spy)
    tel = Telemetry(enabled=True)
    ks = zipf_time_evolving(6 * FEED, num_keys=2_000, z=1.2, flip_head=2_000,
                            seed=0)
    # widen the ids mid-run: the hot mask kept on the device grows too
    ks = np.where(np.arange(ks.shape[0]) < 3 * FEED, ks, ks + (1 << 21))
    _run("fused", "fish", ks, (np.arange(ks.shape[0]) % 5 + 1.0),
         telemetry=tel)
    series = tel.timeline.series
    got = list(zip((p[4] for p in series["fish.hot_set_size"]),
                   (p[4] for p in series["fish.hot_set_churn"])))
    assert len(want) >= 4 and any(c for _, c in want)
    assert got == [(float(a), float(b)) for a, b in want]


def test_hot_set_points_match_a_host_sum_at_2p20_rows():
    """At 2^20 rows the order of the float32 sum matters; the device's hot
    set still equals the host's wherever a key's count is not within a
    relative 1e-4 of the threshold, including keys planted just either
    side of it."""
    rng = np.random.default_rng(5)
    k = 1 << 20
    theta = 1.0 / (4 * 96)  # not a power of two: the product rounds
    trk = (rng.pareto(1.2, k) * 0.3).astype(np.float32)
    near = rng.choice(k, 64, replace=False)
    trk[near] = 0.0
    # the planted counts sum to 64 * theta * total of the final total
    total = float(trk.sum(dtype=np.float64)) / (1 - 64 * theta)
    trk[near[:32]] = np.float32(theta * total * (1 + 1e-3))
    trk[near[32:]] = np.float32(theta * total * (1 - 1e-3))

    def host(t):
        s = float(t.sum())
        return t > theta * s, s

    want, s = host(trk)
    band = np.abs(trk - theta * s) <= 1e-4 * theta * s
    assert not band.any() and want[near[:32]].all() and not want[near[32:]].any()
    prev = rng.random(k) < 0.001
    dev = np.append(trk, np.float32(0.0))  # the phantom row
    size, churn, hot = feed_fused._hot_set_points(dev, prev,
                                                  np.float32(theta))
    assert np.array_equal(np.asarray(hot), want)
    assert int(size) == int(want.sum())
    assert int(churn) == int((want != prev).sum())
