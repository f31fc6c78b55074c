"""Per-kernel Pallas validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import fish_count_ref, ssd_chunked_ref, ssd_ref


# ---------------------------------------------------------------------------
# fish_count
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k_slots,n_keys,block_n", [
    (128, 512, 128),
    (256, 1000, 256),   # non-multiple n -> padding path
    (100, 3000, 1024),  # table needs lane padding
    (1024, 4096, 512),
])
def test_fish_count_shapes(k_slots, n_keys, block_n):
    rng = np.random.default_rng(k_slots + n_keys)
    n_real = k_slots * 3 // 4
    table = np.full(k_slots, -1, np.int32)
    table[:n_real] = rng.choice(10_000, n_real, replace=False)
    keys = rng.integers(0, 12_000, n_keys).astype(np.int32)
    c1, m1 = ops.fish_count(jnp.asarray(table), jnp.asarray(keys),
                            block_n=block_n)
    c2, m2 = fish_count_ref(jnp.asarray(table), jnp.asarray(keys))
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


@given(st.integers(1, 200), st.integers(1, 64), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_fish_count_property(n_keys, n_table, seed):
    rng = np.random.default_rng(seed)
    table = rng.choice(500, n_table, replace=False).astype(np.int32)
    keys = rng.integers(0, 600, n_keys).astype(np.int32)
    counts, matched = ops.fish_count(jnp.asarray(table), jnp.asarray(keys))
    # total matched keys == total counts
    assert int(np.asarray(counts).sum()) == int(np.asarray(matched).sum())
    # every count equals the true occurrence count
    for i, t in enumerate(table):
        assert counts[i] == (keys == t).sum()


def test_fish_count_empty_table():
    table = jnp.full((128,), -1, jnp.int32)
    keys = jnp.arange(100, dtype=jnp.int32)
    counts, matched = ops.fish_count(table, keys)
    assert int(counts.sum()) == 0 and not bool(matched.any())


# ---------------------------------------------------------------------------
# block size: derived from the table width, never changes a result
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width,block_n,want", [
    (128, None, 1024),
    (4_096, None, 256),          # K=4096: the (block_n, K) matrix fits
    (4_096 + 16_384, None, 32),  # fish_epoch_count at K=4096, N=16384
    (128, 100, 64),              # a requested size rounds down to pow2
    (128, 4_096, 1024),          # and never exceeds the default
    (1 << 20, None, 8),          # never below one sublane tile
])
def test_fit_block_n(width, block_n, want):
    from repro.kernels.fish_count import fit_block_n

    assert fit_block_n(width, block_n) == want


@pytest.mark.parametrize("kernel", ("fish_count", "fish_epoch_count",
                                    "store_probe"))
def test_kernels_bit_identical_across_block_sizes(kernel):
    """Every block size gives the same bits, and the oracle's values
    (exactly, but for one rounding of the decayed counters, which XLA
    may contract into a fused multiply-add in the oracle)."""
    from repro.kernels.ref import fish_epoch_count_ref

    rng = np.random.default_rng(11)
    k = 2_048
    table = np.sort(np.concatenate([
        rng.choice(6_000, 1_500, replace=False),
        np.full(k - 1_500, -1)])).astype(np.int32)
    t = jnp.asarray(table)
    c = jnp.asarray(rng.gamma(2.0, 3.0, k).astype(np.float32))
    kk = jnp.asarray(rng.integers(0, 7_000, 1_000).astype(np.int32))
    vv = jnp.asarray(rng.integers(-50, 50, 1_000).astype(np.int32))
    run, oracle = {
        "fish_count": (lambda b: ops.fish_count(t, kk, block_n=b),
                       lambda: fish_count_ref(t, kk)),
        "fish_epoch_count": (
            lambda b: ops.fish_epoch_count(t, c, kk, alpha=0.3, block_n=b),
            lambda: fish_epoch_count_ref(t, c, kk, alpha=0.3)),
        "store_probe": (
            lambda b: ops.store_probe(t, kk, vv, block_n=b, impl="pallas"),
            lambda: ops.store_probe(t, kk, vv, impl="sorted")),
    }[kernel]
    base = [np.asarray(x) for x in run(8)]
    for block_n in (None, 64, 1024):
        for g, b in zip(run(block_n), base):
            np.testing.assert_array_equal(np.asarray(g), b)
    for i, (b, w) in enumerate(zip(base, oracle())):
        if kernel == "fish_epoch_count" and i == 0:
            np.testing.assert_allclose(b, np.asarray(w), rtol=2e-7)
        else:
            np.testing.assert_array_equal(b, np.asarray(w))


# ---------------------------------------------------------------------------
# SSD chunk kernels
# ---------------------------------------------------------------------------


def _ssd_inputs(b, s, h, p, g, n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, s, h, p)).astype(dtype)
    a = (-np.abs(rng.normal(size=(b, s, h))) * 0.1).astype(dtype)
    bb = (rng.normal(size=(b, s, g, n)) * 0.3).astype(dtype)
    cc = (rng.normal(size=(b, s, g, n)) * 0.3).astype(dtype)
    return map(jnp.asarray, (x, a, bb, cc))


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 32, 2, 32, 32),
    (2, 256, 4, 64, 1, 64, 64),
    (1, 128, 8, 64, 4, 32, 128),  # chunk == seq
])
def test_ssd_pallas_vs_sequential(b, s, h, p, g, n, chunk):
    x, a, bb, cc = _ssd_inputs(b, s, h, p, g, n, seed=s + h)
    y_ref, f_ref = ssd_ref(x, a, bb, cc)
    y_k, f_k = ops.ssd_scan(x, a, bb, cc, chunk=chunk, impl="pallas")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_ref_impl_matches_sequential():
    x, a, bb, cc = _ssd_inputs(2, 128, 4, 32, 1, 32, seed=9)
    y_ref, f_ref = ssd_ref(x, a, bb, cc)
    y_c, f_c = ops.ssd_scan(x, a, bb, cc, chunk=32, impl="ref")
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(f_c), np.asarray(f_ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_initial_state_carries():
    """Chunked scan with an initial state == sequential with that state."""
    x, a, bb, cc = _ssd_inputs(1, 64, 2, 16, 1, 16, seed=3)
    s0 = jnp.asarray(np.random.default_rng(1).normal(
        size=(1, 2, 16, 16)).astype(np.float32)) * 0.5
    y_ref, f_ref = ssd_ref(x, a, bb, cc, initial_state=s0)
    y_c, f_c = ssd_chunked_ref(x, a, bb, cc, chunk=16, initial_state=s0)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(f_c), np.asarray(f_ref),
                               rtol=3e-4, atol=3e-4)


def test_ssd_bf16_inputs():
    x, a, bb, cc = _ssd_inputs(1, 64, 2, 16, 1, 16, seed=5)
    y32, _ = ops.ssd_scan(x, a, bb, cc, chunk=16, impl="pallas")
    y16, _ = ops.ssd_scan(x.astype(jnp.bfloat16), a, bb.astype(jnp.bfloat16),
                          cc.astype(jnp.bfloat16), chunk=16, impl="pallas")
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y32),
                               rtol=5e-2, atol=5e-2)


@given(st.integers(1, 3), st.sampled_from([32, 64, 128]),
       st.sampled_from([16, 32, 64]), st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_ssd_property_decay_bounded(b, s, chunk, seed):
    """Property: with zero decay rate (a=0) and b=c=const, SSD degenerates
    to a running sum — outputs must be monotone in t for positive x."""
    h, p, g, n = 2, 16, 1, 8
    chunk = min(chunk, s)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(np.abs(rng.normal(size=(b, s, h, p))).astype(np.float32))
    a = jnp.zeros((b, s, h), jnp.float32)
    ones = jnp.ones((b, s, g, n), jnp.float32) * 0.5
    y, _ = ops.ssd_scan(x, a, ones, ones, chunk=chunk, impl="pallas")
    y = np.asarray(y)
    assert (np.diff(y.sum(axis=(2, 3)), axis=1) >= -1e-3).all()
