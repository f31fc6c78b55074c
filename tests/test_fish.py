"""Unit + property tests for the FISH core algorithms (paper Algs. 1-3)."""

from hypothesis_compat import given, settings, st

from repro.core import EpochFrequencyTracker, FishParams, chk_num_workers
from repro.data.synthetic import zipf_time_evolving


# ---------------------------------------------------------------------------
# Alg. 1 — sequential tracker
# ---------------------------------------------------------------------------


def test_counts_exact_when_under_capacity():
    t = EpochFrequencyTracker(FishParams(alpha=0.5, epoch=10**9, k_max=100))
    keys = [1, 2, 2, 3, 3, 3]
    t.update_many(keys)
    assert t.counts == {1: 1.0, 2: 2.0, 3: 3.0}


def test_replace_min_inherits_count():
    """Alg. 1 line 22: new key gets c_min + 1, not 1."""
    t = EpochFrequencyTracker(FishParams(alpha=0.5, epoch=10**9, k_max=2))
    t.update_many([1, 1, 1, 2])
    t.update(99)  # evicts key 2 (count 1) -> c_99 = 2
    assert 99 in t.counts and t.counts[99] == 2.0
    assert 2 not in t.counts


def test_epoch_decay_applied_every_epoch():
    p = FishParams(alpha=0.5, epoch=4, k_max=10)
    t = EpochFrequencyTracker(p)
    t.update_many([7, 7, 7, 7])      # epoch fills; decay fires on next tuple
    assert t.counts[7] == 4.0
    t.update(7)                      # decay: 4*0.5=2, then +1
    assert t.counts[7] == 3.0
    assert t.epochs_completed == 1


def test_alpha_zero_forgets_everything():
    p = FishParams(alpha=0.0, epoch=2, k_max=10)
    t = EpochFrequencyTracker(p)
    t.update_many([5, 5, 9])
    assert t.counts[9] == 1.0
    assert t.counts.get(5, 0.0) == 0.0  # cleared at the epoch boundary


@given(st.lists(st.integers(0, 50), min_size=1, max_size=500),
       st.integers(2, 20))
@settings(max_examples=50, deadline=None)
def test_spacesaving_error_bound(keys, k_max):
    """SpaceSaving invariant (no decay): count overestimates true frequency
    by at most N/K_max."""
    t = EpochFrequencyTracker(FishParams(alpha=1.0, epoch=10**9, k_max=k_max))
    t.update_many(keys)
    n = len(keys)
    true = {}
    for k in keys:
        true[k] = true.get(k, 0) + 1
    for k, c in t.counts.items():
        assert c >= true.get(k, 0) - 1e-9          # never underestimates
        assert c <= true.get(k, 0) + n / k_max + 1e-9

    assert len(t.counts) <= k_max


@given(st.lists(st.integers(0, 30), min_size=1, max_size=400))
@settings(max_examples=30, deadline=None)
def test_bounded_memory(keys):
    p = FishParams(alpha=0.3, epoch=16, k_max=8)
    t = EpochFrequencyTracker(p)
    t.update_many(keys)
    assert len(t.counts) <= p.k_max


def test_hot_keys_detects_time_evolving_flip():
    """After the ZF hot-set flip (§6.1), the tracker must follow the new head."""
    p = FishParams(alpha=0.2, epoch=1000, k_max=200)
    t = EpochFrequencyTracker(p)
    keys = zipf_time_evolving(30_000, num_keys=5_000, z=1.5, flip_head=1000,
                              seed=1)
    t.update_many(keys[:24_000].tolist())
    hot_before = set(t.hot_keys(16))
    t.update_many(keys[24_000:].tolist())
    hot_after = set(t.hot_keys(16))
    # flipped distribution: Pr[i] ∝ (1000 - i + 1)^-z -> head near key ~999
    assert hot_before, "no hot keys detected before flip"
    assert hot_after, "no hot keys detected after flip"
    assert any(k > 900 for k in hot_after), f"stale hot set: {hot_after}"


# ---------------------------------------------------------------------------
# Alg. 2 — CHK
# ---------------------------------------------------------------------------


def test_chk_nonhot_gets_two_workers():
    d, m = chk_num_workers(0.001, 0.5, theta=0.01, num_workers=64)
    assert d == 2 and m == 0


def test_chk_top_key_gets_all_workers():
    d, m = chk_num_workers(0.5, 0.5, theta=0.01, num_workers=64)
    assert d == 64 and m == 64


def test_chk_power_of_two_hierarchy():
    # f_top/f = 4 -> index 2 -> d = W/4
    d, _ = chk_num_workers(0.1, 0.4, theta=0.01, num_workers=64)
    assert d == 16


def test_chk_monotone_memory():
    # M_k never lets d shrink (Alg. 2 lines 7-10)
    d1, m = chk_num_workers(0.5, 0.5, theta=0.01, num_workers=64, m_k=0)
    d2, m = chk_num_workers(0.05, 0.5, theta=0.01, num_workers=64, m_k=m)
    assert d2 == d1 == 64


@given(st.floats(1e-6, 1.0), st.floats(1e-6, 1.0), st.integers(2, 256))
@settings(max_examples=100, deadline=None)
def test_chk_bounds(f_k, f_top, w):
    f_top = max(f_k, f_top)
    d, _ = chk_num_workers(f_k, f_top, theta=0.25 / w, num_workers=w)
    assert 2 <= d <= w
