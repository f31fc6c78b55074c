"""Pallas TPU kernel: FISH's per-tuple worker choice (the Alg. 3 hotspot).

Each tuple goes to the candidate with the least estimated wait (paper
Eq. 2), and every choice adds one to the chosen worker's ``assigned``
count, which the next tuple's waits read: the choice is sequential by
nature.  As an XLA ``lax.scan`` every step gathers the candidates'
backlog, assigned count and capacity out of the per-worker vectors and
scatters one lane back, six small launches per tuple.

This kernel runs the same sequence in one launch.  The per-worker
vectors stay on-chip across the launch's tuples, and a tuple's
candidates arrive as a lane-dense row of *ranks* over the worker lanes
(``rank[i, w]`` = the position of worker ``w`` among tuple ``i``'s
candidates in ring order, :data:`BIG` where ``w`` is not one of its
first ``d``), so a step is a handful of whole-row vector operations and
two cross-lane minima, with no gather or scatter::

    wait = where(rank < BIG, (backlog + assigned) * ecaps, inf)
    m    = min(wait)
    rmin = min(where(rank < BIG & wait == m, rank, BIG))
    assigned += (rank == rmin) & (rmin < BIG)

The waits are the same float32 products as the scan's
``(backlog[r] + assigned[r]) * ecaps[r]``, and the least rank among the
equal minima is the scan's ``argmin`` tie rule (first candidate in ring
order, an all-inf row included), so both pick the same worker for every
tuple.  A row with no candidate (a padding tuple) picks rank
:data:`BIG` and assigns nothing.

Tuples stream through the grid in blocks, sequentially; ``assigned`` is
an output block resident across the grid.  The chosen ranks are
collected 128 to a lane-dense output row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fish_choose", "BIG", "LANES"]

#: rank of a worker that is not among a tuple's candidates, and the
#: chosen rank of a tuple with none; above any real rank
BIG = 2 ** 30
LANES = 128  # tuples per output row, and the lane multiple of the rows
_BLOCK_N = 1024  # tuples per grid step, at most


def _fish_choose_kernel(rank_ref, backlog_ref, ecaps_ref, assigned0_ref,
                        chosen_ref, assigned_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        assigned_ref[...] = assigned0_ref[...]

    backlog = backlog_ref[...]  # (1, L) f32, resident
    ecaps = ecaps_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def out_row(g, asn):
        def step(j, carry):
            asn, picked = carry
            r = rank_ref[pl.ds(g * LANES + j, 1), :]  # (1, L) int32
            cand = r < BIG
            wait = jnp.where(cand, (backlog + asn) * ecaps, jnp.inf)
            m = jnp.min(wait, axis=1, keepdims=True)
            rmin = jnp.min(jnp.where(cand & (wait == m), r, BIG), axis=1,
                           keepdims=True)
            asn = asn + jnp.where((r == rmin) & (rmin < BIG), 1.0, 0.0)
            return asn, jnp.where(lane == j, rmin, picked)

        asn, picked = jax.lax.fori_loop(
            0, LANES, step, (asn, jnp.full((1, LANES), BIG, jnp.int32)))
        chosen_ref[pl.ds(g, 1), :] = picked
        return asn

    assigned_ref[...] = jax.lax.fori_loop(
        0, rank_ref.shape[0] // LANES, out_row, assigned_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fish_choose(rank, backlog, ecaps, assigned, *, interpret: bool = False):
    """Sequential least-wait choice over lane-dense candidate ranks.

    rank:      (N, L) int32, L a multiple of 128: ``rank[i, w]`` is
               worker ``w``'s position among tuple ``i``'s candidates,
               :data:`BIG` where it is not a candidate.
    backlog:   (L,) float32 estimated backlog per worker lane.
    ecaps:     (L,) float32 estimated service time per worker lane.
    assigned:  (L,) float32 tuples assigned since the last tick.
    returns:   chosen (N,) int32, the rank each tuple picked (:data:`BIG`
               for a row with no candidate), and the (L,) ``assigned``
               after every tuple's choice.
    """
    n, width = rank.shape
    if width % LANES:
        raise ValueError(f"rank rows of {width} lanes: not whole "
                         f"{LANES}-lane rows")
    # whole output rows; the padding rows hold no candidate
    n_tot = max(-(-n // LANES), 1) * LANES
    block_n = min(_BLOCK_N, n_tot)
    n_tot = -(-n_tot // block_n) * block_n
    rank = jnp.pad(rank, ((0, n_tot - n), (0, 0)), constant_values=BIG)

    def lane_row(v):
        return v.astype(jnp.float32).reshape(1, width)

    def resident():
        return pl.BlockSpec((1, width), lambda i: (0, 0))

    chosen, assigned = pl.pallas_call(
        _fish_choose_kernel,
        grid=(n_tot // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, width), lambda i: (i, 0)),  # tuple tile
            resident(), resident(), resident(),
        ],
        out_specs=[
            pl.BlockSpec((block_n // LANES, LANES), lambda i: (i, 0)),
            resident(),  # accumulated across the grid
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tot // LANES, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, width), jnp.float32),
        ],
        # each block's choices read the assigned counts the previous
        # block left: the grid is one sequence
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rank, lane_row(backlog), lane_row(ecaps), lane_row(assigned))
    return chosen.reshape(-1)[:n], assigned[0]
