"""Pallas TPU kernel: FISH intra-epoch match-and-count (the Alg. 1 hotspot).

Every tuple of an epoch must be compared against the bounded counter table
``K`` (paper Alg. 1 line 8: ``if k in K``).  Sequential SpaceSaving does this
tuple-by-tuple; on TPU we batch the whole epoch: the O(N_epoch × K_max)
comparison matrix is evaluated block-by-block on the VPU with the token axis
tiled through VMEM, producing

* ``counts``  — per-table-slot occurrence counts for this epoch
  (Alg. 1 line 9, batched), and
* ``matched`` — per-token membership flags (drives the batched ReplaceMin
  merge done by the caller — see ``repro.core.fish.epoch_update``).

The table (K_max ≤ a few thousand ids) stays resident in VMEM across the
whole grid; only token blocks stream HBM→VMEM.  Arithmetic intensity is
~K_max compares per 4-byte token load, so the kernel is firmly compute-bound
on the VPU — exactly the term the paper's epoch batching is designed to
shrink (one decay pass per epoch instead of per tuple).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["fish_count", "fish_epoch_count"]

_BLOCK_N = 1024  # tokens per grid step (VMEM tile), at most
#: elements one grid step's compare matrices may hold: 4 MiB at 32 bits,
#: a quarter of the 16 MiB of VMEM a TPU kernel may scope by default
_VMEM_ELEMS = 1 << 20


def fit_block_n(width: int, block_n: Optional[int] = None) -> int:
    """Tokens per grid step such that the ``(block_n, width)`` compare
    matrices stay inside :data:`_VMEM_ELEMS`: the largest power of two
    that is at most ``block_n`` and :data:`_BLOCK_N` and fits, never
    below one sublane tile of 8.  Sums over blocks are exact integer
    counts, so the choice never changes a result."""
    b = _BLOCK_N if block_n is None else min(int(block_n), _BLOCK_N)
    b = 1 << (max(b, 8).bit_length() - 1)
    while b > 8 and b * width > _VMEM_ELEMS:
        b //= 2
    return b


def _fish_count_kernel(table_ref, keys_ref, counts_ref, matched_ref):
    step = pl.program_id(0)
    tbl = table_ref[...]  # (1, K) int32, resident
    ks = keys_ref[...]  # (block_n, 1) int32

    eq = (ks == tbl) & (tbl >= 0)  # (block_n, K) — the O(N·K) hotspot

    @pl.when(step == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    counts_ref[...] += jnp.sum(eq.astype(jnp.float32), axis=0, keepdims=True)
    matched_ref[...] = jnp.any(eq, axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fish_count(
    table_keys: jnp.ndarray,
    batch_keys: jnp.ndarray,
    *,
    block_n: Optional[int] = None,
    interpret: bool = False,
):
    """Blocked epoch match-and-count.

    table_keys: (K,) int32, -1 marks empty slots.  K should be a multiple of
                128 for TPU lane alignment (the wrapper in ops.py pads).
    batch_keys: (N,) int32 tuple/key ids (>= 0).
    block_n:    tokens per grid step; capped by :func:`fit_block_n`.
    returns:    counts (K,) float32, matched (N,) bool.
    """
    k = table_keys.shape[0]
    n = batch_keys.shape[0]
    block_n = fit_block_n(k, block_n)
    n_pad = -n % block_n
    keys2d = jnp.pad(batch_keys, (0, n_pad), constant_values=-2).reshape(-1, 1)
    table2d = table_keys.reshape(1, k)
    grid = (keys2d.shape[0] // block_n,)

    counts, matched = pl.pallas_call(
        _fish_count_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # table resident in VMEM
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),  # token tile
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # accumulated across grid
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((keys2d.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(table2d, keys2d)
    return counts[0], matched[:n, 0].astype(bool)


# ---------------------------------------------------------------------------
# Fused epoch kernel (ISSUE 1): decay + match-count + candidate histogram
# ---------------------------------------------------------------------------


def _fish_epoch_kernel(alpha, block_n, table_ref, counts_ref, keys_ref,
                       all_keys_ref, new_counts_ref, matched_ref, cand_ref,
                       first_ref):
    step = pl.program_id(0)
    tbl = table_ref[...]  # (1, K) int32, resident
    ks = keys_ref[...]  # (block_n, 1) int32
    all_k = all_keys_ref[...]  # (1, N_pad) int32, resident

    eq = (ks == tbl) & (tbl >= 0)  # (block_n, K) — the O(N·K) hotspot

    @pl.when(step == 0)
    def _init():
        new_counts_ref[...] = jnp.zeros_like(new_counts_ref)

    # the epoch delta accumulates as exact integer counts, and the decayed
    # counters join it once at the last step, so the result is the
    # oracle's alpha*counts + delta whatever block_n is
    new_counts_ref[...] += jnp.sum(eq.astype(jnp.float32), axis=0,
                                   keepdims=True)

    @pl.when(step == pl.num_programs(0) - 1)
    def _decay():
        # inter-epoch TimeDecayingUpdate fused into the same launch
        new_counts_ref[...] = (counts_ref[...] * jnp.float32(alpha)
                               + new_counts_ref[...])

    matched_ref[...] = jnp.any(eq, axis=1, keepdims=True).astype(jnp.int32)

    # candidate epoch histogram: occurrences of each token's key within the
    # whole epoch batch (O(N_epoch) per token on the VPU), plus a
    # first-occurrence flag so the caller can dedupe without a host sort
    eq_all = (ks == all_k) & (all_k >= 0)  # (block_n, N_pad)
    cand_ref[...] = jnp.sum(eq_all.astype(jnp.float32), axis=1, keepdims=True)
    gid = step * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (ks.shape[0], 1), 0
    )
    col = jax.lax.broadcasted_iota(jnp.int32, eq_all.shape, 1)
    earlier = eq_all & (col < gid)
    first_ref[...] = (
        jnp.sum(earlier.astype(jnp.int32), axis=1, keepdims=True) == 0
    ).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("alpha", "block_n", "interpret")
)
def fish_epoch_count(
    table_keys: jnp.ndarray,
    table_counts: jnp.ndarray,
    batch_keys: jnp.ndarray,
    *,
    alpha: float,
    block_n: Optional[int] = None,
    interpret: bool = False,
):
    """One fused launch for a whole epoch (ISSUE 1 tentpole):

    1. inter-epoch decay      — ``counts * alpha`` (Alg. 1 lines 23-26),
    2. intra-epoch counting   — per-slot occurrence counts + match flags
       (Alg. 1 lines 8-9, the O(N_epoch × K_max) hotspot), and
    3. candidate histogram    — per-token epoch frequency of *its own* key
       plus a first-occurrence flag, which is exactly the unmatched-new-key
       histogram the batched ReplaceMin needs (replaces the host-side
       sort + segment-count pass in ``epoch_update``).

    The candidate histogram costs O(N_epoch²) compares and keeps the whole
    padded epoch resident in VMEM, so this kernel is sized for the paper's
    epoch regime (N_epoch ≈ 1e3-1e4: ≤ ~1e8 VPU compares, tens of KB
    resident).  For much larger epochs, split the batch into several
    epoch-sized calls or fall back to the unfused `epoch_update` path,
    whose candidate pass is O(N log N) on host.

    table_keys:  (K,) int32, -1 marks empty slots (K: multiple of 128 for
                 lane alignment — ops.py pads).
    table_counts:(K,) float32 decayed counters.
    batch_keys:  (N,) int32 key ids (>= 0).
    block_n:     tokens per grid step; capped by :func:`fit_block_n` over
                 both compare matrices.
    returns:     new_counts (K,) f32 = alpha*counts + epoch delta,
                 matched (N,) bool, cand_count (N,) f32, is_first (N,) bool.
    """
    k = table_keys.shape[0]
    n = batch_keys.shape[0]
    # each step holds a (block_n, K) and a (block_n, n_tot) compare
    # matrix; n_tot is n padded to the block, at most n rounded up to
    # _BLOCK_N
    block_n = fit_block_n(k + (-(-n // _BLOCK_N) * _BLOCK_N), block_n)
    n_pad = -n % block_n
    keys2d = jnp.pad(batch_keys, (0, n_pad), constant_values=-2).reshape(-1, 1)
    all2d = keys2d.reshape(1, -1)
    table2d = table_keys.reshape(1, k)
    counts2d = table_counts.astype(jnp.float32).reshape(1, k)
    n_tot = keys2d.shape[0]
    grid = (n_tot // block_n,)

    kern = functools.partial(_fish_epoch_kernel, alpha, block_n)
    new_counts, matched, cand, first = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # table resident
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # counters resident
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),  # token tile
            pl.BlockSpec((1, n_tot), lambda i: (0, 0)),  # whole epoch resident
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # accumulated over grid
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((n_tot, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_tot, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_tot, 1), jnp.int32),
        ],
        interpret=interpret,
    )(table2d, counts2d, keys2d, all2d)
    return (
        new_counts[0],
        matched[:n, 0].astype(bool),
        cand[:n, 0],
        first[:n, 0].astype(bool),
    )
