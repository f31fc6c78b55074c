"""Pure-jnp oracles for the SSD kernels (exact, unblocked)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_ref", "ssd_chunked_ref"]


def ssd_ref(x, a, b, c, initial_state=None):
    """Exact sequential SSD recurrence (oracle for the chunked kernels).

    x: (B, S, H, P); a: (B, S, H) log decay; b, c: (B, S, G, N).
    returns y (B, S, H, P), final_state (B, H, N, P), all float32.
    """
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    hpg = h // g
    bh = jnp.repeat(b, hpg, axis=2)  # (B, S, H, N)
    ch = jnp.repeat(c, hpg, axis=2)

    def step(state, inp):
        xt, at, bt, ct = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        state = state * jnp.exp(at)[..., None, None] + (
            bt[..., :, None] * xt[..., None, :]
        )  # (B,H,N,P)
        yt = jnp.einsum("bhn,bhnp->bhp", ct, state)
        return state, yt

    state0 = (
        jnp.zeros((bsz, h, n, p), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(a, 1, 0).astype(jnp.float32),
        jnp.moveaxis(bh, 1, 0).astype(jnp.float32),
        jnp.moveaxis(ch, 1, 0).astype(jnp.float32),
    )
    final, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1), final


def ssd_chunked_ref(x, a, b, c, chunk: int, initial_state=None):
    """Chunked-math oracle (same algorithm as the kernels, pure jnp).

    Used to separate "chunking math correct" from "Pallas tiling correct".
    Shapes as in ssd_ref.
    """
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    hpg = h // g

    xc = x.reshape(bsz, nc, chunk, h, p).astype(jnp.float32)
    ac = a.reshape(bsz, nc, chunk, h).astype(jnp.float32)
    bc_ = b.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    cc = c.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    bh = jnp.repeat(bc_, hpg, axis=3)  # (B,NC,Q,H,N)
    ch = jnp.repeat(cc, hpg, axis=3)

    a_cum = jnp.cumsum(ac, axis=2)  # inclusive, (B,NC,Q,H)
    a_tot = a_cum[:, :, -1, :]  # (B,NC,H)

    # per-chunk states
    decay = jnp.exp(a_tot[:, :, None, :] - a_cum)  # (B,NC,Q,H)
    states = jnp.einsum("bnqh,bnqhk,bnqhp->bnhkp", decay, bh, xc)  # k=N

    # scan across chunks
    def comb(prev, inp):
        st, at = inp
        new = prev * jnp.exp(at)[..., None, None] + st
        return new, prev  # emit state *entering* the chunk

    s0 = (
        jnp.zeros((bsz, h, n, p), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    final, prev_states = jax.lax.scan(
        comb, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(a_tot, 1, 0))
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,NC,H,N,P)

    # chunk-local quadratic part + carried contribution
    rel = a_cum[:, :, :, None, :] - a_cum[:, :, None, :, :]  # (B,NC,Q,Q,H)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    l_mat = jnp.where(mask[None, None, :, :, None], jnp.exp(rel), 0.0)
    scores = jnp.einsum("bnqhk,bnshk->bnqsh", ch, bh)  # (B,NC,Q,Q,H)
    y_diag = jnp.einsum("bnqsh,bnshp->bnqhp", scores * l_mat, xc)
    y_off = jnp.einsum(
        "bnqhk,bnqh,bnhkp->bnqhp", ch, jnp.exp(a_cum), prev_states
    )
    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y, final
