"""Regression tests for >2^31 aggregates (ISSUE 10 satellite 1).

The state stores hold int64 totals, and the fused feed uploads
per-worker counts as int32, so counts past 2^31 must survive the
rebase/readback round trip.  Both feed billing
(``size_bytes``/``MigrationBiller``), which is where silent wraparound
would turn into silently-wrong charges.
"""
import numpy as np
import pytest

from repro.core.stream import simulate_edge
from repro.state.migration import MigrationBiller, MigrationStats
from repro.state.store import ENTRY_BYTES, ArrayStateStore
from repro.topology.configs import config_for

INT32_MAX = 2 ** 31 - 1


def test_array_store_totals_past_int32():
    st = ArrayStateStore()
    chunk = 2 ** 30
    for _ in range(3):
        st.merge_entries(np.array([1], dtype=np.int64),
                         np.array([chunk], dtype=np.int64),
                         np.array([chunk], dtype=np.int64))
    _, vs, cs = st.items()
    assert vs.tolist() == [3 * chunk] and cs.tolist() == [3 * chunk]


def test_fused_counts_survive_int32_rebase():
    """A grouper whose lifetime per-worker counts already exceed int32
    must route identically to a fresh one (pkg compares counts only
    pairwise) and read exact counts back from the fused kernel."""
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 500, size=4_000)
    offset = 2 ** 31 + 5

    g_fresh = config_for("pkg").build(8)
    g_aged = config_for("pkg").build(8)
    g_aged.assigned_counts += offset  # uniform: preserves comparisons
    assert g_aged.assigned_counts.dtype == np.int64

    r_fresh = simulate_edge(g_fresh, keys, arrival_rate=2e4, mode="fused",
                            capacities=np.full(8, 4e-4))
    r_aged = simulate_edge(g_aged, keys, arrival_rate=2e4, mode="fused",
                           capacities=np.full(8, 4e-4))
    deltas = g_aged.assigned_counts - offset
    np.testing.assert_array_equal(deltas, g_fresh.assigned_counts)
    assert int(g_aged.assigned_counts.max()) > INT32_MAX
    assert int(deltas.sum()) == keys.shape[0]
    np.testing.assert_array_equal(r_aged.finishes, r_fresh.finishes)


def test_fused_rejects_int32_breaking_count_spread():
    """A non-uniform spread the rebase cannot absorb fails loudly, not
    with wraparound."""
    rng = np.random.default_rng(6)
    keys = rng.integers(0, 500, size=1_000)
    g = config_for("pkg").build(8)
    g.assigned_counts[0] += 2 ** 31 + 5  # spread itself exceeds int32
    with pytest.raises(ValueError, match="int32"):
        simulate_edge(g, keys, arrival_rate=2e4, mode="fused",
                      capacities=np.full(8, 4e-4))


def test_migration_bill_exact_past_int32_entries():
    """A synthetic >2^31 entry count billed through MigrationBiller must
    charge the exact amount (host path is int64/float, no wrap)."""
    entries = 2 ** 31 + 9
    stats = MigrationStats()
    stats.last_recv_entries = {2: entries}
    biller = MigrationBiller(stats, cost_per_byte=1.0)
    biller.on_event("post_membership", None)
    charges = biller.pop_charges()
    assert charges == {2: float(entries * ENTRY_BYTES)}
    assert biller.billed_total == float(entries * ENTRY_BYTES)
    # and the stats byte counter itself is a plain int, not a wrapped one
    stats.bytes_moved += entries * ENTRY_BYTES
    assert stats.bytes_moved == entries * ENTRY_BYTES > INT32_MAX
