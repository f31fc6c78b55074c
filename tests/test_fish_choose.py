"""FISH's per-tuple choice kernel against the sequential scan it replaced.

The fused segment picks each FISH tuple's worker (Alg. 3 Eq. 2, the
least estimated wait among its first d ring candidates) in one Pallas
launch over lane-dense candidate ranks
(:mod:`repro.kernels.fish_choose`, here in interpret mode).  The oracle
is the ``lax.scan`` the segment ran before, carried here verbatim: for
every tuple the kernel must pick the same worker, and leave the same
``assigned`` vector, ties, infinite waits, short ring rows and padding
tuples included.  A fused FISH session routed by the kernel must equal
one routed by the scan, and stay within the §6 bands of the batched
engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import zipf_time_evolving
from repro.kernels import feed_fused
from repro.kernels import fish_choose as fc
from repro.obs.telemetry import Telemetry
from repro.state import WindowOp, direct_aggregate
from repro.topology import (Edge, SimulatorEngine, Source, Stage, Topology,
                            config_for)


def _scan_choice(a, row, d, backlog, assigned):
    """The segment's FISH choice before the kernel, verbatim."""
    dmax = row.shape[1]
    iota_d = jnp.arange(dmax, dtype=jnp.int32)

    def step(asn, x):
        r, dd, v = x
        waits = jnp.where((iota_d < dd) & (r >= 0),
                          (backlog[r] + asn[r]) * a["ecaps"][r], jnp.inf)
        w = r[jnp.argmin(waits)]
        w = jnp.where(v, w, a["phantom_w"])
        return asn.at[w].add(jnp.where(v, 1.0, 0.0)), w

    assigned, workers = jax.lax.scan(step, assigned, (row, d, a["valid"]))
    return workers, assigned


_scan_jit = jax.jit(_scan_choice)
_kernel_jit = jax.jit(feed_fused._choose_fish)

CASES = ("ties", "mixed_caps", "all_inf", "padded_rows")
SHAPES = [(n_pad, nw) for n_pad in (64, 1_024, 16_384)
          for nw in (5, 128, 200)]
RING = 96  # ring positions


def _inputs(case, n_pad, nw, seed=7):
    """A segment's choice inputs: a ring table of distinct owners (rows
    short of live owners padded with -1), ring positions, d in [2, dmax],
    an invalid tail, and estimator vectors over nw + 1 lanes (the last
    the phantom)."""
    rng = np.random.default_rng(seed + n_pad + nw)
    dmax = max(nw, 2)
    live = nw if case != "padded_rows" else max(2, nw * 2 // 3)
    owners = rng.permutation(nw)[:live]
    cands = np.full((RING, dmax), -1, np.int32)
    for r in range(RING):
        cands[r, :live] = rng.permutation(owners)
    w1 = nw + 1
    if case == "ties":
        backlog = rng.integers(0, 3, w1).astype(np.float32)
        ecaps = np.ones(w1, np.float32)
    elif case == "all_inf":
        backlog = np.full(w1, np.inf, np.float32)
        ecaps = np.ones(w1, np.float32)
    else:
        backlog = rng.uniform(0.0, 8.0, w1).astype(np.float32)
        backlog[rng.random(w1) < 0.1] = np.inf  # a few infinite waits
        ecaps = rng.choice(np.float32([0.25, 0.5, 1.0, 1.5, 3.0]), w1)
    assigned = rng.integers(0, 4, w1).astype(np.float32)
    m = n_pad - n_pad // 8 - 1  # an invalid tail
    a = {"rank_of": jnp.asarray(feed_fused._build_rank_table(cands, nw)),
         "ring_idx": jnp.asarray(rng.integers(0, RING, n_pad, np.int32)),
         "ecaps": jnp.asarray(ecaps),
         "valid": jnp.asarray(np.arange(n_pad) < m),
         "phantom_w": jnp.int32(nw)}
    row = jnp.asarray(cands)[a["ring_idx"]]
    d = jnp.asarray(rng.integers(2, dmax + 1, n_pad, dtype=np.int32))
    return a, row, d, jnp.asarray(backlog), jnp.asarray(assigned)


@pytest.mark.parametrize("n_pad,nw", SHAPES,
                         ids=[f"n{n}-w{w}" for n, w in SHAPES])
@pytest.mark.parametrize("case", CASES)
def test_kernel_picks_as_the_scan(case, n_pad, nw):
    a, row, d, backlog, assigned = _inputs(case, n_pad, nw)
    w_scan, asn_scan = _scan_jit(a, row, d, backlog, assigned)
    w_kern, asn_kern = _kernel_jit(a, row, d, backlog, assigned)
    np.testing.assert_array_equal(np.asarray(w_kern), np.asarray(w_scan))
    np.testing.assert_array_equal(np.asarray(asn_kern), np.asarray(asn_scan))
    valid = np.asarray(a["valid"])
    assert (np.asarray(w_kern)[~valid] == nw).all()  # padding: phantom
    if case == "all_inf":
        # every wait infinite: the first candidate in ring order
        np.testing.assert_array_equal(np.asarray(w_kern)[valid],
                                      np.asarray(row)[valid, 0])


def test_rank_table_inverts_the_ring_rows():
    cands = np.array([[3, 0, 1, -1], [1, 3, -1, -1]], np.int32)
    rank = feed_fused._build_rank_table(cands, 4)
    assert rank.shape == (2, fc.LANES)
    big = fc.BIG
    assert rank[0, :5].tolist() == [1, 2, big, 0, big]
    assert rank[1, :5].tolist() == [big, 0, big, 1, big]
    assert (rank[:, 4:] == big).all()
    assert feed_fused._build_rank_table(cands[:, :2], 200).shape == (2, 256)


# ---------------------------------------------------------------------------
# end to end: a fused FISH session
# ---------------------------------------------------------------------------


WINDOW = 1_500


@pytest.fixture(scope="module")
def stream():
    keys = zipf_time_evolving(12_000, num_keys=900, z=1.3, flip_head=300,
                              seed=3)
    vals = np.random.default_rng(4).integers(1, 10, keys.shape[0])
    return keys, vals.astype(np.int64)


def _session(mode, stream, telemetry=None, feeds=4):
    keys, vals = stream
    op = WindowOp(agg="sum", value="payload", size=WINDOW)
    topo = Topology(name="fish-choose",
                    stages=(Stage("agg", 12, operator=op),),
                    edges=(Edge("source", "agg", config_for("fish")),))
    sess = SimulatorEngine(mode=mode).open(topo, arrival_rate=3e4,
                                           telemetry=telemetry)
    src = Source(keys, arrival_rate=3e4, values=vals)
    for batch in src.iter_batches(batch_size=-(-keys.shape[0] // feeds)):
        sess.feed(batch)
    return sess.close(), op


def test_fused_session_routes_as_the_scan(stream, monkeypatch):
    tel = Telemetry(enabled=True)
    rk, op = _session("fused", stream, telemetry=tel)
    with monkeypatch.context() as mp:
        mp.setattr(feed_fused, "_choose_fish", _scan_choice)
        mp.setattr(feed_fused, "_SEG_CACHE", {})
        rs, _ = _session("fused", stream)
    ek, es = rk.edges[0], rs.edges[0]
    assert ek.n_tuples == es.n_tuples == stream[0].shape[0]
    assert ek.imbalance == es.imbalance
    assert ek.memory_overhead == es.memory_overhead
    assert ek.execution_time == es.execution_time
    assert ek.latency_p99 == es.latency_p99
    # the same tuples on the same workers: the same partial entries
    assert rk.state["agg"]["partials"] == rs.state["agg"]["partials"]
    assert rk.state["agg"]["merged"] == direct_aggregate(
        stream[0], op, values=stream[1])
    # every FISH tuple went through the kernel
    snap = tel.metrics.snapshot()
    assert snap["fused.choice_kernel_tuples"]["value"] == ek.n_tuples


def test_fused_session_within_bands_of_batched(stream):
    rk, op = _session("fused", stream)
    rb, _ = _session("batched", stream)
    ek, eb = rk.edges[0], rb.edges[0]
    assert ek.n_tuples == eb.n_tuples
    assert ek.execution_time == pytest.approx(eb.execution_time, rel=0.05)
    assert ek.throughput == pytest.approx(eb.throughput, rel=0.05)
    assert ek.memory_overhead == pytest.approx(eb.memory_overhead, rel=0.25)
    assert ek.imbalance <= eb.imbalance + 0.05
    assert rk.state["agg"]["merged"] == rb.state["agg"]["merged"]
