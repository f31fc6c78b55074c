"""Jit'd public wrappers around the Pallas kernels.

On non-TPU backends the kernels run in ``interpret=True`` mode (Pallas
executes the kernel body in Python on CPU) so every call site is portable;
on TPU the same BlockSpecs compile to Mosaic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import fish_choose as _fish_choose
from . import ssd as _ssd
from . import ref as ref  # re-exported for tests/benchmarks

__all__ = ["fish_choose", "ssd_scan", "ref"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fish_choose(rank: jnp.ndarray, backlog: jnp.ndarray, ecaps: jnp.ndarray,
                assigned: jnp.ndarray):
    """FISH's sequential least-wait choice over lane-dense candidate
    ranks ``rank`` (N, L), L a multiple of 128.  The per-worker vectors
    (backlog, service time, assigned) may be shorter than L: they are
    padded with workers that are never a candidate.  Returns each
    tuple's chosen rank (``fish_choose.BIG`` for none) and the updated
    ``assigned`` at its own length."""
    nw = assigned.shape[0]
    pad = rank.shape[1] - nw
    chosen, asn = _fish_choose.fish_choose(
        rank, *(jnp.pad(v, (0, pad)) for v in (backlog, ecaps, assigned)),
        interpret=_interpret())
    return chosen, asn[:nw]


def ssd_scan(x, a, b, c, *, chunk: int = 128, initial_state=None,
             impl: str = None):
    """Full SSD layer scan: chunk kernels + tiny cross-chunk lax.scan.

    x: (B, S, H, P); a: (B, S, H) log decay (<= 0); b, c: (B, S, G, N).
    returns y (B, S, H, P) f32, final_state (B, H, N, P) f32.

    impl: "pallas" | "ref" | None.  None = pallas on TPU (the target), the
    pure-jnp chunked reference elsewhere (mathematically identical; Pallas
    tiling is validated in interpret mode by tests/test_kernels.py).
    """
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"

    # pad seq to a chunk multiple: zero x/b/c with zero log-decay leaves the
    # carried state untouched through the padding steps
    s_orig = x.shape[1]
    pad = -s_orig % chunk
    if pad:
        padt = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, a, b, c = padt(x), padt(a), padt(b), padt(c)

    if impl == "ref":
        y, final = ref.ssd_chunked_ref(x, a, b, c, chunk,
                                       initial_state=initial_state)
        return y[:, :s_orig], final

    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc = s // chunk
    interp = _interpret()

    xc = x.reshape(bsz * nc, chunk, h, p).astype(jnp.float32)
    ac = a.reshape(bsz * nc, chunk, h).astype(jnp.float32)
    bc_ = b.reshape(bsz * nc, chunk, g, n).astype(jnp.float32)
    cc = c.reshape(bsz * nc, chunk, g, n).astype(jnp.float32)
    a_cum = jnp.cumsum(ac, axis=1)

    states, a_tot = _ssd.ssd_chunk_state(xc, bc_, a_cum, interpret=interp)
    states = states.reshape(bsz, nc, h, n, p)
    a_tot = a_tot.reshape(bsz, nc, h)

    def comb(prev, inp):
        st, at = inp
        return prev * jnp.exp(at)[..., None, None] + st, prev

    s0 = (
        jnp.zeros((bsz, h, n, p), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    final, prev_states = jax.lax.scan(
        comb, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(a_tot, 1, 0))
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1).reshape(bsz * nc, h, n, p)

    y = _ssd.ssd_chunk_output(xc, bc_, cc, a_cum, prev_states, interpret=interp)
    return y.reshape(bsz, s, h, p)[:, :s_orig], final
