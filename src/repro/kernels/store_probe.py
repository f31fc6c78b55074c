"""Pallas kernel: keyed-state probe/accumulate (ISSUE 6 tentpole, part c).

The per-worker keyed state store is a table of key slots; folding a routed
chunk into it is "for each tuple, find its key's slot and accumulate
(value, count)".  The sequential form probes per tuple; here the whole
chunk is batched with the same slot discipline as
:mod:`repro.kernels.fish_count`: the O(N_chunk × K_slots) key-vs-slot
comparison matrix is evaluated block-by-block on the VPU with the token
axis tiled through VMEM, producing per-slot accumulated sums

* ``vsum``    — Σ value over the chunk's tuples landing in each slot,
* ``csum``    — tuple count per slot, and
* ``matched`` — per-token hit flags (misses are new keys the caller
  inserts host-side before re-probing — the open-addressing slow path).

The slot table stays resident in VMEM across the grid (the bounded-scope
insight again: a pane's live key set is small); only token blocks stream
HBM→VMEM.  Accumulation is int32 so merged aggregates stay exact — the
state-store contract (order-independent int sums) must survive the device
round-trip bit-identically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fish_count import fit_block_n

__all__ = ["store_probe"]


def _store_probe_kernel(table_ref, keys_ref, vals_ref, vsum_ref, csum_ref,
                        matched_ref):
    step = pl.program_id(0)
    tbl = table_ref[...]  # (1, K) int32, resident
    ks = keys_ref[...]  # (block_n, 1) int32
    vs = vals_ref[...]  # (block_n, 1) int32

    eq = (ks == tbl) & (tbl >= 0)  # (block_n, K) — the probe matrix

    @pl.when(step == 0)
    def _init():
        vsum_ref[...] = jnp.zeros_like(vsum_ref)
        csum_ref[...] = jnp.zeros_like(csum_ref)

    vsum_ref[...] += jnp.sum(jnp.where(eq, vs, 0), axis=0, keepdims=True)
    csum_ref[...] += jnp.sum(eq.astype(jnp.int32), axis=0, keepdims=True)
    matched_ref[...] = jnp.any(eq, axis=1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def store_probe(
    table_keys: jnp.ndarray,
    batch_keys: jnp.ndarray,
    batch_vals: jnp.ndarray,
    *,
    block_n: Optional[int] = None,
    interpret: bool = False,
):
    """Blocked probe/accumulate of one routed chunk against a slot table.

    table_keys: (K,) int32 slot keys, -1 marks empty slots.  K should be a
                multiple of 128 for TPU lane alignment (ops.py pads).
    batch_keys: (N,) int32 tuple key ids (>= 0).
    batch_vals: (N,) int32 per-tuple values (``repro.state.window.
                tuple_values`` folded to int32 — the caller guards range).
    block_n:    tokens per grid step; capped by :func:`~repro.kernels.
                fish_count.fit_block_n`.
    returns:    vsum (K,) int32, csum (K,) int32, matched (N,) bool.
    """
    k = table_keys.shape[0]
    n = batch_keys.shape[0]
    block_n = fit_block_n(k, block_n)
    n_pad = -n % block_n
    keys2d = jnp.pad(batch_keys, (0, n_pad), constant_values=-2).reshape(-1, 1)
    vals2d = jnp.pad(batch_vals, (0, n_pad)).reshape(-1, 1)
    table2d = table_keys.reshape(1, k)
    grid = (keys2d.shape[0] // block_n,)

    vsum, csum, matched = pl.pallas_call(
        _store_probe_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # table resident in VMEM
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),  # token tile
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),  # value tile
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (0, 0)),  # accumulated across grid
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((keys2d.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(table2d, keys2d, vals2d)
    return vsum[0], csum[0], matched[:n, 0].astype(bool)
