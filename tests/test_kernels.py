"""Per-kernel Pallas validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import ssd_chunked_ref, ssd_ref


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
