"""Batched grouping engine vs the sequential reference oracle (ISSUE 1).

Contract (DESIGN.md §6):

* SG / FG / PKG — *identical* assignments and metrics: the batched paths are
  exact vectorisations (round-robin arithmetic, cached unique-key hashes,
  cumulative-count two-choice loop).
* DC / WC / FISH — *bounded divergence*: frequencies are read at sub-chunk
  granularity and Alg. 3 is water-filled per unique key, so individual
  assignments may differ but the paper's metrics must stay within tight
  bands of the oracle.
"""

import numpy as np
import pytest

from repro.analysis.contracts import DRIFT_SCHEMES, EXACT_SCHEMES
from repro.core import simulate_edge
from repro.topology import build_grouper
from repro.data.synthetic import intern_keys, zipf_time_evolving


def _sim_batched(g, keys, **kw):
    return simulate_edge(g, keys, mode="batched", **kw).metrics


def _sim_reference(g, keys, **kw):
    return simulate_edge(g, keys, mode="reference", **kw).metrics


@pytest.fixture(scope="module")
def keys():
    return zipf_time_evolving(30_000, num_keys=3_000, z=1.4, seed=0)


def _pair(scheme, keys, workers=16, **kw):
    m_ref = _sim_reference(
        build_grouper(scheme, workers), keys, arrival_rate=2e4, **kw
    )
    m_bat = _sim_batched(
        build_grouper(scheme, workers), keys, arrival_rate=2e4, **kw
    )
    return m_ref, m_bat


# ---------------------------------------------------------------------------
# assign_batch-level equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
def test_assign_batch_exact(scheme, keys):
    g_ref = build_grouper(scheme, 16)
    seq = np.array([g_ref.assign(k, i * 5e-5) for i, k in enumerate(keys)])
    g_bat = build_grouper(scheme, 16)
    bat = g_bat.assign_batch(keys, 0.0, 5e-5)
    np.testing.assert_array_equal(seq, bat)
    np.testing.assert_array_equal(g_ref.assigned_counts, g_bat.assigned_counts)
    assert g_ref.memory_overhead() == g_bat.memory_overhead()


@pytest.mark.parametrize("scheme", DRIFT_SCHEMES)
def test_assign_batch_bounded_drift(scheme, keys):
    g_ref = build_grouper(scheme, 16)
    for i, k in enumerate(keys):
        g_ref.assign(k, i * 5e-5)
    g_bat = build_grouper(scheme, 16)
    g_bat.assign_batch(keys, 0.0, 5e-5)
    c_ref = g_ref.assigned_counts.astype(float)
    c_bat = g_bat.assigned_counts.astype(float)
    # per-worker assigned mass within 15% of the oracle's
    np.testing.assert_allclose(c_bat, c_ref, rtol=0.15, atol=50)
    # replica memory within 20%
    assert g_bat.memory_overhead() == pytest.approx(
        g_ref.memory_overhead(), rel=0.20
    )


# ---------------------------------------------------------------------------
# simulator-level equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", EXACT_SCHEMES)
def test_simulator_metrics_identical(scheme, keys):
    m_ref, m_bat = _pair(scheme, keys)
    for field, v_ref in m_ref.row().items():
        assert m_bat.row()[field] == pytest.approx(v_ref, rel=1e-9), field
    np.testing.assert_allclose(m_bat.per_worker_busy, m_ref.per_worker_busy,
                               rtol=1e-9)


@pytest.mark.parametrize("scheme", DRIFT_SCHEMES)
def test_simulator_metrics_bounded(scheme, keys):
    m_ref, m_bat = _pair(scheme, keys)
    assert m_bat.execution_time == pytest.approx(m_ref.execution_time, rel=0.05)
    assert m_bat.throughput == pytest.approx(m_ref.throughput, rel=0.05)
    assert m_bat.memory_overhead == pytest.approx(m_ref.memory_overhead,
                                                  rel=0.20)
    # load balance must not degrade materially vs the oracle
    assert m_bat.imbalance <= m_ref.imbalance + 0.05
    # queueing latency stays the same order of magnitude
    assert m_bat.latency_p99 <= max(m_ref.latency_p99 * 10.0, 0.05)


def test_simulator_object_keys_fall_back():
    """Non-integer keys take the reference path — loudly (ISSUE 5): the
    10-20x slowdown warns with the offending dtype/shape."""
    str_keys = np.array([f"k{i % 7}" for i in range(300)], dtype=object)
    with pytest.warns(UserWarning, match=r"falling back.*dtype=object.*"
                                         r"shape=\(300,\)"):
        m = _sim_batched(build_grouper("pkg", 4), str_keys, arrival_rate=1e3)
    assert m.execution_time > 0

    # interned ids take the batched path and stay exact vs their own oracle
    ids, vocab = intern_keys(str_keys)
    assert ids.dtype == np.int32 and vocab.shape[0] == 7
    m_bat = _sim_batched(build_grouper("pkg", 4), ids, arrival_rate=1e3)
    m_ref = _sim_reference(build_grouper("pkg", 4), ids,
                                      arrival_rate=1e3)
    assert m_bat.execution_time == pytest.approx(m_ref.execution_time)


def test_assign_batch_and_pipeline_accept_object_keys():
    """String keys must keep working through the batch paths (the caches
    are dtype-agnostic; only replica recording needs the slow path)."""
    from repro.data.pipeline import StreamingPipeline

    str_keys = np.array(["a", "b", "a", "c", "b", "a"] * 40, dtype=object)
    for scheme in EXACT_SCHEMES + DRIFT_SCHEMES:
        g = build_grouper(scheme, 4)
        workers = g.assign_batch(str_keys, 0.0, 1e-4)
        assert workers.shape == str_keys.shape
        assert set(g.replicas) == {"a", "b", "c"}

    pipe = StreamingPipeline(4, 8, 2, grouping="fg")
    pipe.ingest_stream(iter([("docA", np.arange(3)), ("docB", np.arange(2))]))
    assert pipe.memory_overhead() == 2


def test_sampling_and_heterogeneous_capacities_match(keys):
    caps = np.concatenate([np.full(8, 2.0), np.full(8, 1.0)]) * 0.9 * 16 / 2e4
    m_ref, m_bat = _pair("fg", keys[:20_000], capacities=caps,
                         sample_every=4_000)
    for field, v_ref in m_ref.row().items():
        assert m_bat.row()[field] == pytest.approx(v_ref, rel=1e-9), field


# ---------------------------------------------------------------------------
# vectorised CHK vs the scalar Alg. 2
# ---------------------------------------------------------------------------


def test_chk_batch_matches_scalar_elementwise():
    from repro.core import chk_num_workers
    from repro.core.fish import chk_num_workers_batch

    rng = np.random.default_rng(11)
    for w in (2, 16, 64, 256):
        theta = 0.25 / w
        f = np.concatenate([
            rng.uniform(0.0, 1.0, 200),
            np.array([0.0, theta, np.nextafter(theta, 1.0), 1.0]),
        ])
        f_top = float(f.max())
        m_prev = rng.integers(0, w + 1, f.shape[0])
        d_b, m_b = chk_num_workers_batch(f, f_top, theta, w, m_k=m_prev)
        for i in range(f.shape[0]):
            d_s, m_s = chk_num_workers(float(f[i]), f_top, theta, w,
                                       m_k=int(m_prev[i]))
            assert (int(d_b[i]), int(m_b[i])) == (d_s, m_s), (i, f[i])
