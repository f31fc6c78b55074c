"""Device-resident fused feed hot path (ISSUE 6 tentpole).

One jitted launch per (edge, segment) chains the three layers the batched
engine runs as separate host passes:

a. **routing** — all six schemes as ``jax.numpy`` ops over device state:
   SG is round-robin arithmetic; FG/PKG/DC/WC/FISH look their candidates
   up in a precomputed consistent-hash ring table (``searchsorted`` over
   the ring points — the device mirror of ``chash.lookup_n``); PKG runs
   the exact sequential two-choice ``lax.scan``; DC/WC/FISH classify hot
   keys against a device-resident dense frequency tracker (the decayed
   epoch counting of Alg. 1, over the per-key table the CHK pass reads)
   and pick per tuple: DC/WC via a masked-argmin scan, FISH via one
   Pallas kernel launch (``kernels/fish_choose.py``) that takes the Eq. 2
   wait-time argmin against the Alg. 3 estimator state over lane-dense
   candidate-rank rows, tuple by tuple;
b. **FIFO** — the closed-form per-worker recurrence solved on device,
   either as one ``lax.scan`` (exact, the CPU default) or as
   ``jax.lax.associative_scan`` over a segmented maximum-accumulate
   (``fifo_impl="assoc"``, the depth-log parallel form, default on TPU);
c. **keyed-state update** — per-(worker, pane slot) aggregate tables
   updated by scatter-add inside the same launch (the host gives each key
   a slot the first time it appears in the open pane, so the table
   follows the pane's tuples, not the key space); panes sync to the host
   :class:`~repro.state.window.KeyedStateManager` only at pane boundaries
   and membership events (``merge_entries`` accumulates, so a pane can be
   synced mid-way and continue on zeroed device tables exactly).

A steady-state ``session.feed(batch)`` is therefore **one** device
dispatch (counted in :attr:`FusedEdgeRunner.dispatches`, surfaced as
``EdgeResult.dispatches``): per-key state (tracker, CHK memory, pane
tables) stays device-resident across feeds; only the small per-worker
vectors (busy, counts, estimator) and the per-tuple finish times and
workers cross the boundary as part of the launch round-trip.  The
replica sets are kept on the host, from the (worker, key) pairs those
workers name.

Shape discipline: segment lengths pad to power-of-two buckets (min
:data:`MIN_BUCKET`) so varying RecordBatch lengths reuse one trace;
:data:`TRACE_COUNT` increments per trace for the compile-count
regression test.  Everything sized per-key is a dense table of
``key_capacity + 1`` rows (row = key id, last row = phantom absorbing
the padding lanes), the pane has ``slots + 1`` rows (last = phantom
slot), everything per-worker has ``busy_len + 1`` lanes (last = phantom
worker).  Worker-universe or key-capacity growth and ring rebuilds with
a different point count change static shapes and recompile — rare,
documented in DESIGN.md §11.

Semantics vs the reference oracle (DESIGN.md §6): SG/FG/PKG routing,
counts, replicas and window aggregates are exact (timing carries an f32
epsilon from the on-device relative clock); DC/WC read frequencies at
segment granularity and FISH once per FISH epoch, from a dense
(unbounded) tracker, and FISH ticks its estimator at segment starts —
bounded drift, same class as the batched engine's sub-chunking.
"""

from __future__ import annotations

import sys as _sys
import types as _types
from hashlib import sha1 as _sha1
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.metrics import GLOBAL_METRICS
from ..obs.telemetry import Telemetry
from . import fish_choose as _fish_choose
from . import ops as _ops


__all__ = ["FusedEdgeRunner", "fused_reject_reason", "TRACE_COUNT",
           "MIN_BUCKET", "KEY_CAP_LIMIT"]

#: The compile-count regression probe, absorbed into the metrics registry
#: (ISSUE 9): ``feed_fused.TRACE_COUNT`` remains readable *and* writable as
#: a module attribute (a property on the module class at the bottom of this
#: file), but the cell itself is this process-wide registry counter —
#: retraces are a property of the jit cache, not of any one session.
_TRACE_COUNTER = GLOBAL_METRICS.counter("fused.trace_count")

#: Shared disabled bundle for runners no session bound telemetry to.
_NULL_TELEMETRY = Telemetry(enabled=False)
MIN_BUCKET = 64  # smallest pow2 padding bucket for segment lengths
#: Dense per-key tables; larger key ids fall back.  At 2^24 + 1 rows the
#: per-key device tables of a 128-worker edge (tracker, candidate memory,
#: 67 MB each) and of its 32-worker merge edge fit one 16 GB chip with
#: room for a growth copy; the host's replica bitmap of the 128-worker
#: edge is 2.16 GB.
KEY_CAP_LIMIT = 1 << 24

_SEG_CACHE: dict = {}  # static signature -> jitted segment function

_SCHEMES = ("sg", "fg", "pkg", "dc", "wc", "fish")
_RING_SCHEMES = ("fg", "pkg", "dc", "wc", "fish")
# a Python int, not a jnp scalar: a device constant built at import
# would claim the accelerator before any caller asked for it
_BIG_I32 = 2 ** 30


def _bucket(n: int) -> int:
    """Smallest power of two >= n that is >= MIN_BUCKET."""
    return max(MIN_BUCKET, 1 << (int(n) - 1).bit_length())


def _pow2_at_least(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def fused_reject_reason(grouper, keys_arr: np.ndarray,
                        values: Optional[np.ndarray],
                        state_sink, tuple_observer) -> Optional[str]:
    """Why this feed cannot run fused (None = it can).  Checked per feed;
    any reason makes the edge fall back to the batched engine for good."""
    scheme = getattr(grouper, "name", None)
    if scheme not in _SCHEMES:
        return f"scheme {scheme!r} has no fused routing"
    if scheme == "fish" and not getattr(grouper, "use_consistent_hash", True):
        return "fused FISH requires the consistent-hash candidate path"
    if tuple_observer is not None:
        return ("fused mode feeds keyed state through state_sink, not "
                "tuple_observer")
    if keys_arr.shape[0]:
        kmin = int(keys_arr.min())
        kmax = int(keys_arr.max())
        if kmin < 0:
            return "fused key tables are dense; negative key ids"
        if kmax >= KEY_CAP_LIMIT:
            return (f"fused key tables are dense; key id {kmax} exceeds "
                    f"capacity limit {KEY_CAP_LIMIT}")
    if state_sink is not None:
        from ..state.window import tuple_values

        op = state_sink.op
        vals = tuple_values(op, keys_arr, payload=values)
        if vals.shape[0]:
            lim = (2 ** 31 - 1) // max(op.stride, 1)
            if int(np.abs(vals).max()) > lim:
                return ("pane aggregates could overflow int32: "
                        f"|value| > {lim} at stride {op.stride}")
    return None


# ---------------------------------------------------------------------------
# ring candidate table — the device mirror of chash.lookup_n
# ---------------------------------------------------------------------------


def _build_ring_table(ring, dmax: int):
    """(sorted ring points uint32, (R, dmax) int32 first-d-distinct-owners).

    ``searchsorted(points, h, side='right') % R`` lands on the same ring
    position as ``bisect_right`` + wrap in ``chash.lookup``; row r holds
    the first ``dmax`` distinct owners walking clockwise from position r —
    exactly ``lookup_n``'s prefix for every d <= dmax.  Rebuilt host-side
    only on membership change (the ring only changes there); rows are
    padded with -1 past the number of distinct live owners.
    """
    pts_l = ring._points
    r_n = len(pts_l)
    pts = np.asarray(pts_l, dtype=np.uint32)
    owners = [ring._owner[p] for p in pts_l]
    d_eff = min(dmax, len(set(owners)))
    cands = np.full((r_n, dmax), -1, dtype=np.int32)
    for r in range(r_n):
        seen = set()
        out = []
        i = r
        while len(out) < d_eff:
            o = owners[i]
            if o not in seen:
                seen.add(o)
                out.append(o)
            i += 1
            if i == r_n:
                i = 0
        cands[r, :d_eff] = out
    return pts, cands


def _build_rank_table(cands, workers: int):
    """(R, L) int32 inverse of the candidate table, for FISH's choice
    kernel: ``rank[r, cands[r, j]] = j``, :data:`fish_choose.BIG`
    elsewhere, over ``L`` = the worker universe rounded up to whole
    128-lane rows.  A ring row holds distinct owners, so each worker has
    at most one rank per row."""
    lanes = -(-workers // _fish_choose.LANES) * _fish_choose.LANES
    rank = np.full((cands.shape[0], lanes), _fish_choose.BIG, np.int32)
    rr, jj = np.nonzero(cands >= 0)
    rank[rr, cands[rr, jj]] = jj
    return rank


# ---------------------------------------------------------------------------
# traced segment bodies
# ---------------------------------------------------------------------------


def _fifo_scan(busy, caps, workers, t):
    """Exact sequential FIFO: f_i = max(busy[w_i], t_i) + caps[w_i]."""

    def step(b, x):
        w, tt = x
        f = jnp.maximum(b[w], tt) + caps[w]
        return b.at[w].set(f), f

    return jax.lax.scan(step, busy, (workers, t))


def _fifo_assoc(busy, caps, workers, t):
    """Closed-form FIFO via ``associative_scan`` (ISSUE 6 tentpole, part b).

    Sort by worker (stable), then within a worker's run of rank j the
    recurrence unrolls to ``f_j = (j+1)P + max(b0, cummax_j(t_k - kP))``;
    the inner cummax is a segmented maximum-accumulate keyed on the worker
    id, evaluated in O(log n) depth.  Equal to :func:`_fifo_scan` up to
    f32 rounding."""
    n = workers.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    order = jnp.argsort(workers)  # stable in jnp
    ws = workers[order]
    ts = t[order]
    first = jnp.concatenate([jnp.ones((1,), bool), ws[1:] != ws[:-1]])
    seg_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(first, iota, 0))
    j = (iota - seg_start).astype(jnp.float32)
    capw = caps[ws]
    g = ts - j * capw

    def comb(a, b):
        aw, ag = a
        bw, bg = b
        return bw, jnp.where(aw == bw, jnp.maximum(ag, bg), bg)

    _, m = jax.lax.associative_scan(comb, (ws, g))
    f = (j + 1.0) * capw + jnp.maximum(busy[ws], m)
    fin = jnp.zeros_like(f).at[order].set(f)
    return busy.at[ws].max(f), fin


def _ring_idx(a):
    """(n_pad,) ring position of each tuple's hashed key.

    Key→position is fixed between membership changes, so when the key
    table is smaller than the segment the ring walk runs once per *key*
    (over the dense hash cache) and tuples gather their key's position —
    ~4× fewer binary-search probes at 16k-tuple segments.  Phantom-row
    gathers clamp (JAX OOB semantics) and are masked off by ``valid``."""
    r_n = a["pts"].shape[0]
    if "hash_arr" in a:
        idx = jnp.searchsorted(a["pts"], a["hash_arr"], side="right") % r_n
        return idx[a["keys"]]
    return jnp.searchsorted(a["pts"], a["h"], side="right") % r_n


def _ring_rows(a, width=None):
    """(n_pad, width or dmax) candidate rows at the segment's ring
    positions (``a["ring_idx"]``).  Schemes with a fixed fanout (fg: 1,
    pkg: 2) pass ``width`` so the per-tuple gather moves ``width``
    candidates instead of the full dmax row."""
    cands = a["cands"] if width is None else a["cands"][:, :width]
    return cands[a["ring_idx"]]


def _route_pkg(a, row):
    """Exact sequential two-choice with cumulative counts (tie -> first)."""
    c0 = row[:, 0]
    c1 = jnp.where(row[:, 1] >= 0, row[:, 1], row[:, 0])

    def step(counts, x):
        a0, a1, v = x
        w = jnp.where(counts[a0] <= counts[a1], a0, a1)
        w = jnp.where(v, w, a["phantom_w"])
        return counts.at[w].add(v.astype(jnp.int32)), w

    return jax.lax.scan(step, a["counts"], (c0, c1, a["valid"]))


def _tracker_update(a, scheme):
    """Dense per-key frequency tracker update, mirroring the batched
    engine's update-then-classify sub-chunk order.  Returns (trk, f per
    tuple, f_top per tuple or scalar)."""
    one = jnp.where(a["valid"], 1.0, 0.0)
    if scheme == "fish":
        # one update-then-classify step per FISH epoch inside the segment,
        # as the batched engine cuts its sub-chunks: a tuple reads the
        # frequencies of its own epoch, so a key that was hot before a
        # hot-set change inside the segment stays hot for its earlier
        # tuples.  cexp_t counts the epoch boundaries at or before tuple
        # t (the boundary decay fires before the tuple is counted);
        # pre_decay covers a segment starting exactly on a boundary.
        cexp = ((a["g0"] + jnp.arange(a["valid"].shape[0], dtype=jnp.int32))
                // a["epoch"]) - (a["g0"] // a["epoch"]) + a["pre_decay"]
        keys = a["keys"]

        def epoch_step(j, carry):
            trk, f, f_top = carry
            trk = trk * jnp.where(j > 0, a["alpha"], 1.0)
            in_j = a["valid"] & (cexp == j)
            trk = trk.at[keys].add(jnp.where(in_j, 1.0, 0.0))
            total = jnp.sum(trk)
            f = jnp.where(in_j & (total > 0.0), trk[keys] / total, f)
            f_top = jnp.where(in_j & (total > 0.0), jnp.max(trk) / total,
                              f_top)
            return trk, f, f_top

        zeros = jnp.zeros_like(one)
        return jax.lax.fori_loop(
            0, a["c_total"].astype(jnp.int32) + 1, epoch_step,
            (a["trk"], zeros, zeros))
    # dc/wc: no decay (reference tracker runs alpha=1, epoch=2^62)
    trk = a["trk"].at[a["keys"]].add(one)
    total = jnp.sum(trk)
    f = jnp.where(total > 0.0, trk[a["keys"]] / total, 0.0)
    f_top = jnp.where(total > 0.0, jnp.max(trk) / total, 0.0)
    return trk, f, f_top


def _route_dcwc(a, row, scheme):
    """DC/WC: hot keys spread over d ring candidates (DC) or the whole
    live set (WC); light keys are the exact PKG two-choice.  One masked-
    argmin ``lax.scan`` over cumulative counts mirrors the sequential
    least-loaded selection (argmin tie -> first candidate in ring order,
    matching ``min(cl, key=counts.__getitem__)``; WC's full-set argmin
    tie -> smallest worker id, matching the (count, id) heap)."""
    with jax.named_scope("route/tracker"):
        trk, f, _ = _tracker_update(a, scheme)
    with jax.named_scope("route/choose"):
        hot = f > a["theta"]
        wnum = a["wnum"]  # live worker-universe size (traced; can grow)
        d_heavy = jnp.clip(jnp.ceil(f * wnum / jnp.sqrt(a["theta"])),
                           2.0, wnum).astype(jnp.int32)
        d = jnp.where(hot, d_heavy, 2)
        dmax = row.shape[1]
        iota_d = jnp.arange(dmax, dtype=jnp.int32)

        def step(counts, x):
            r, dd, h, v = x
            waits = jnp.where((iota_d < dd) & (r >= 0), counts[r], _BIG_I32)
            w = r[jnp.argmin(waits)]
            if scheme == "wc":
                full = jnp.where(a["act_mask"], counts, _BIG_I32)
                w = jnp.where(h, jnp.argmin(full).astype(w.dtype), w)
            w = jnp.where(v, w, a["phantom_w"])
            return counts.at[w].add(v.astype(jnp.int32)), w

        counts, workers = jax.lax.scan(
            step, a["counts"], (row, d, hot, a["valid"]))
    return counts, workers, trk


def _choose_fish(a, row, d, backlog, assigned):
    """Alg. 3 Eq. 2 for every tuple of the segment, in stream order, in
    one :func:`~repro.kernels.fish_choose.fish_choose` launch: each
    tuple's candidates are a lane-dense row of ranks over the worker
    lanes (the inverse ring table's row at its ring position, the
    phantom lane left out), :data:`~repro.kernels.fish_choose.BIG` past
    its first ``d`` and on padding tuples.  Returns each tuple's worker
    (the phantom lane on padding) and the estimator's ``assigned`` after
    the segment."""
    big = _fish_choose.BIG
    n_w = backlog.shape[0] - 1
    rank = a["rank_of"][a["ring_idx"]]
    rank = jnp.where((rank < d[:, None]) & a["valid"][:, None], rank, big)
    chosen, asn = _ops.fish_choose(rank, backlog[:n_w], a["ecaps"][:n_w],
                                   assigned[:n_w])
    picked = jnp.take_along_axis(
        row, jnp.minimum(chosen, row.shape[1] - 1)[:, None], axis=1)[:, 0]
    workers = jnp.where(chosen < big, picked, a["phantom_w"])
    return workers, assigned.at[:n_w].set(asn)


def _route_fish(a, row):
    """FISH: Alg. 1 (dense decayed tracker) + Alg. 2 (CHK with monotone
    memory M_k) + Alg. 3 (per-tuple Eq. 2 wait-time argmin against the
    estimator state) — the per-tuple oracle's selection with frequencies
    read once per FISH epoch."""
    with jax.named_scope("route/tracker"):
        trk, f, f_top = _tracker_update(a, "fish")
    with jax.named_scope("route/choose"):
        hot = (f > a["theta"]) & (f > 0.0) & (f_top > 0.0)
        ratio = jnp.maximum(f_top / jnp.maximum(f, 1e-30), 1.0)
        index = jnp.clip(jnp.floor(jnp.log2(ratio)), 0.0, 30.0)
        wnum = a["wnum"]
        d0 = jnp.clip(jnp.floor(wnum / jnp.exp2(index)),
                      a["d_min"].astype(jnp.float32), wnum).astype(jnp.int32)
        m_prev = a["m_k"][a["keys"]]
        d = jnp.where(hot, jnp.maximum(d0, m_prev), 2)
        m_k = a["m_k"].at[a["keys"]].max(
            jnp.where(hot & a["valid"], jnp.maximum(m_prev, d0), 0))

        # estimator tick (Alg. 3 Eq. 1), applied once at segment start when due
        backlog, assigned = a["ebl"], a["eas"]
        work = (backlog + assigned) * a["ecaps"]
        ticked = jnp.where(work > a["elapsed"],
                           (work - a["elapsed"]) / a["ecaps"], 0.0)
        backlog = jnp.where(a["do_tick"] > 0, ticked, backlog)
        assigned = jnp.where(a["do_tick"] > 0, 0.0, assigned)

        workers, assigned = _choose_fish(a, row, d, backlog, assigned)
        lanes = jnp.arange(a["counts"].shape[0], dtype=workers.dtype)
        counts = a["counts"] + jnp.sum(
            (workers[None, :] == lanes[:, None]) & a["valid"][None, :],
            axis=1).astype(jnp.int32)
    return counts, workers, trk, m_k, backlog, assigned


def _get_seg_fn(sig):
    """Build (or fetch) the jitted segment function for one static shape
    signature — (scheme, padded length, worker lanes, key rows, ring
    points, candidate width, pane?, fresh pane?, fifo impl, pane slot
    rows) is the recompile boundary.

    Each phase runs under a ``jax.named_scope`` (``route/ring``,
    ``route/tracker``, ``route/choose``, ``fifo``, and with a pane
    ``pane/scatter`` and ``pane/last``), which names its operations in a
    device trace's ``tf_op`` metadata; the compiled program is the same.
    Every launch returns each tuple's worker (``workers``), from which the
    host keeps the replica sets and the pane's touched entries.  The
    module is ``jit_seg_<scheme>``."""
    fn = _SEG_CACHE.get(sig)
    if fn is not None:
        return fn
    scheme, n_pad, w1, kcap1, r_n, dmax, has_pane, reset, fifo_impl, s1 = sig
    phantom_w = w1 - 1
    fifo = _fifo_scan if fifo_impl == "scan" else _fifo_assoc

    def seg(dev, a):
        # `dev` holds the per-key device tables (tracker, candidate
        # memory, pane planes) — donated, so XLA updates them in place
        # instead of copying the ~MB accumulators every launch
        _TRACE_COUNTER.add(1)  # runs at trace time only
        a = dict(a)
        a.update(dev)
        a["phantom_w"] = jnp.int32(phantom_w)
        # padding is always the array tail, so validity is derived from
        # the live count instead of shipping a bool lane per tuple
        a["valid"] = jnp.arange(n_pad, dtype=jnp.int32) < a["m"]
        out = {}
        trk = None
        def _count(workers):
            # dense broadcast-sum: ~3x cheaper than a 1-lane scatter on
            # the CPU backend at these worker counts
            lanes = jnp.arange(w1, dtype=jnp.int32)
            seg = ((workers[None, :] == lanes[:, None])
                   & a["valid"][None, :]).sum(axis=1, dtype=jnp.int32)
            return a["counts"] + seg

        # DC/WC/FISH scope their tracker and choice phases themselves
        if scheme == "sg":
            with jax.named_scope("route/choose"):
                iota = jnp.arange(n_pad, dtype=jnp.int32)
                workers = a["act"][(a["rr"] + iota) % a["a_live"]]
                workers = jnp.where(a["valid"], workers, phantom_w)
                counts = _count(workers)
        else:
            with jax.named_scope("route/ring"):
                a["ring_idx"] = _ring_idx(a)
                row = _ring_rows(a, {"fg": 1, "pkg": 2}.get(scheme))
            if scheme == "fg":
                with jax.named_scope("route/choose"):
                    workers = jnp.where(a["valid"], row[:, 0], phantom_w)
                    counts = _count(workers)
            elif scheme == "pkg":
                with jax.named_scope("route/choose"):
                    counts, workers = _route_pkg(a, row)
            elif scheme in ("dc", "wc"):
                counts, workers, trk = _route_dcwc(a, row, scheme)
            else:  # fish
                (counts, workers, trk, m_k, backlog,
                 assigned) = _route_fish(a, row)
                out["m_k"] = m_k
                out["ebl"] = backlog
                out["eas"] = assigned
        if trk is not None:
            out["trk"] = trk

        with jax.named_scope("fifo"):
            busy, fin = fifo(a["busy"], a["caps"], workers, a["t"])
        out["fin"] = fin
        out["busy"] = busy
        out["counts"] = counts
        # each tuple's worker: with the host's keys it names the launch's
        # (worker, key) replica pairs and, with a pane, every entry the
        # pane touched, so the flush gathers those alone
        out["workers"] = workers
        if has_pane:
            with jax.named_scope("pane/scatter"):
                # one stacked scatter updates value and count planes
                # together, through a flat row index (1-D indexed scatters
                # lower to a cheaper XLA scatter than 2-D ones on CPU)
                vc = jnp.stack([jnp.where(a["valid"], a["vals"], 0),
                                a["valid"].astype(jnp.int32)], axis=-1)
                # worker-major flat index into the (w1, s1) planes, by the
                # pane slot the host gave each tuple's key
                flat = workers * s1 + a["slots"]
                # `reset` marks the first segment of a pane: the tables
                # start from in-jit zeros (a fused memset) instead of
                # round-tripping an eagerly allocated zero buffer through
                # the launch
                base = (jnp.zeros((w1 * s1, 2), jnp.int32) if reset
                        else a["pane_tab"].reshape(w1 * s1, 2))
                # indices are in-bounds by construction (the phantom
                # worker lane and phantom slot row absorb padding), so
                # skipping the per-element bounds check measurably speeds
                # the CPU scatter
                pane = base.at[flat].add(
                    vc, mode="promise_in_bounds").reshape(w1, s1, 2)
            out["pane_tab"] = pane
            with jax.named_scope("pane/last"):
                gidx = a["seg_base"] + jnp.arange(n_pad, dtype=jnp.int32)
                gidx = jnp.where(a["valid"], gidx, -1)
                lanes = jnp.arange(w1, dtype=jnp.int32)
                seg_last = jnp.max(
                    jnp.where(workers[None, :] == lanes[:, None],
                              gidx[None, :], -1), axis=1)
                out["pane_last"] = (seg_last if reset else
                                    jnp.maximum(a["pane_last"], seg_last))
        return out

    # the per-scheme name makes the module `jit_seg_<scheme>`
    seg.__name__ = seg.__qualname__ = f"seg_{scheme}"
    fn = _SEG_CACHE[sig] = jax.jit(seg, donate_argnums=0)
    return fn


def pane_gather(tab, ws, slots):
    """The (value, count) entries of the (w1, s1, 2) pane table at the
    (worker, slot) pairs ``(ws[i], slots[i])``, one row each.  The 2-D
    index reads the table in its own layout, never reshaped; padding pairs
    name the phantom lane and slot row, which only ever hold zeros.  The
    module is ``jit_pane_gather``, outside the ``jit_seg`` programs."""
    return tab.at[ws, slots].get(mode="promise_in_bounds")


def hot_set_points(trk, prev, theta):
    """FISH's hot set at an epoch point, on the device: the keys whose
    tracker share exceeds ``theta`` (the phantom row dropped), how many
    there are, and how many entered or left since the ``prev`` mask.

    The total is a float32 sum in XLA's reduction order, which is not
    numpy's: the two may differ in their last bits, so a key whose count
    lies within that rounding (a relative 1e-6 or so at 2^24 rows) of
    ``theta * total`` may be classed on the other side than a host sum
    would class it.  The routing's own classification is float32 too."""
    t = trk[:-1]
    total = jnp.sum(t)
    hot = (t > theta * total) & (total > 0.0)
    return jnp.sum(hot), jnp.sum(hot != prev), hot


_hot_set_points = jax.jit(hot_set_points)


_pane_gather = jax.jit(pane_gather)


# ---------------------------------------------------------------------------
# the per-edge runner (device state residency across feeds)
# ---------------------------------------------------------------------------


class FusedEdgeRunner:
    """Device-resident execution state of one fused edge.

    Lives on ``EdgeState.device`` across feeds.  Per-key state —
    frequency tracker, CHK memory, open pane tables — stays on device
    between launches; per-worker vectors (busy, counts, estimator)
    round-trip with each launch as arguments/outputs, keeping the host
    copies authoritative so event handling and metrics never need a
    separate sync.

    The replica sets never reach the device.  Each launch hands back its
    tuples' workers, and the host folds the (worker, key) pairs into a
    bitmap of the pairs seen so far (``(w1, kcap + 1)`` bool,
    worker-major), remembering the pairs seen for the first time: at the
    pane flush, from the pairs it dedupes anyway; on an edge without a
    pane, at each launch's readback.  ``host_sync`` adds those new pairs
    to ``grouper.replicas`` — called before metrics/close and membership
    events.

    The open pane is keyed by slot, not by key id: the host gives each key
    the next free slot the first time it appears in the pane (a dense
    key -> slot map, reset at the keys it touched when the pane is
    flushed).  A pane never holds more tuples than the sink's window
    stride, so its distinct keys, and the table's rows, are at most
    ``min(_bucket(stride), key capacity)`` at any key space.
    """

    def __init__(self, grouper, state, sink, telemetry=None):
        self.scheme = grouper.name
        self.has_pane = sink is not None
        self._pane_tuples = sink.op.stride if sink is not None else 0
        self.fifo_impl = ("assoc" if jax.default_backend() == "tpu"
                          else "scan")
        # ISSUE 9: launch/pane counters live in the metrics registry; the
        # legacy ``dispatches`` attribute is a property over the counter
        # (per-feed window on a cumulative cell — see ``begin_feed``)
        self.tel = telemetry if telemetry is not None else _NULL_TELEMETRY
        self._c_dispatches = self.tel.metrics.counter(
            "fused.dispatches", scheme=self.scheme)
        self._c_pane_flushes = self.tel.metrics.counter(
            "fused.pane_flushes", scheme=self.scheme)
        self._c_host_syncs = self.tel.metrics.counter(
            "fused.host_syncs", scheme=self.scheme)
        self._c_pane_gathers = self.tel.metrics.counter(
            "fused.pane_gathers", scheme=self.scheme)
        self._c_key_regrows = self.tel.metrics.counter(
            "fused.key_regrows", scheme=self.scheme)
        # tuples FISH's choice kernel routed (every FISH launch's)
        self._c_choice_tuples = self.tel.metrics.counter(
            "fused.choice_kernel_tuples", scheme=self.scheme)
        self._feed_base_dispatches = 0
        self._prev_hot = None     # fish hot mask at the last epoch point
        self._fish_epoch_idx = -1
        self._fish_epochs_crossed = 0
        self.pane_fed = 0         # tuples in the device pane, unsynced
        # (workers, keys) of the open pane's valid tuples, one pair of
        # arrays per launch: the (w, k) entries the flush gathers
        self._pane_pairs: list = []
        self._slot_of = None      # dense key -> pane slot + 1 (0 = none)
        self._n_slots = 0         # slots in use in the open pane
        self._slots = 0           # slot rows of the pane table (pow2)
        self._kcap = 0
        self._w1 = 0
        self._dmax = 1 if self.scheme == "fg" else (
            2 if self.scheme == "pkg" else 0)  # 0 = worker-universe width
        self._pts = None          # ring points (np uint32)
        self._cands = None        # ring candidate rows (np int32)
        self._pts_dev = None
        self._cands_dev = None
        self._rank_dev = None     # FISH: inverse ring table (device)
        self._hash_arr = None     # dense key -> hash32 cache (np uint32)
        self._hash_ok = None
        # (w1, kcap + 1) bool: the (worker, key) replica pairs seen so far
        self._repl_seen = None
        # (workers, keys) of the pairs first seen since the last host_sync
        self._repl_new: list = []
        self._c_repl_new = self.tel.metrics.counter(
            "fused.replica_pairs_new", scheme=self.scheme)
        # device-resident per-key state
        self.trk = None
        self.m_k = None
        self.pane_tab = None      # (w1, slots + 1, 2): value / count planes
        self.pane_last = None

    @property
    def dispatches(self) -> int:
        """Launches in the current feed (the ``EdgeResult.dispatches``
        source) — a per-feed window on the registry's cumulative
        ``fused.dispatches`` counter, so the registry and the report can
        never disagree."""
        return self._c_dispatches.value - self._feed_base_dispatches

    # -- shape management (the recompile boundary; rare) --------------------
    def _ensure_shapes(self, grouper, state, kmax: int) -> None:
        w1 = state.busy_until.shape[0] + 1
        new_kcap = self._kcap
        if kmax >= new_kcap:
            new_kcap = _pow2_at_least(max(kmax + 1, MIN_BUCKET))
        if w1 == self._w1 and new_kcap == self._kcap:
            return
        old_k, old_w, old_s = self._kcap, self._w1, self._slots
        if new_kcap != old_k:
            with self.tel.tracer.span("fused.key_regrow", cat="fused",
                                      kcap=new_kcap) as regrow_span:
                self._grow_keys(old_k, new_kcap)
                self._grow_repl(old_k, old_w, new_kcap, w1)
                regrow_span.set(bytes=self._key_table_bytes())
            self._c_key_regrows.add(1)
        else:
            self._grow_repl(old_k, old_w, new_kcap, w1)
        if self.has_pane:
            # the pane never holds more distinct keys than its tuples or
            # than the key capacity, whichever is fewer
            self._slots = min(_bucket(self._pane_tuples), new_kcap)
            if self.pane_tab is not None and (w1, self._slots) != (old_w,
                                                                  old_s):
                # an empty (flushed) pane stays None — the next launch's
                # `reset` variant rebuilds it at the new shape from zeros
                self.pane_tab = _grow_pane(self.pane_tab, old_w, old_s, w1,
                                           self._slots)
                self.pane_last = _grow_last(self.pane_last, old_w, w1)
        grew_w = w1 != self._w1
        self._kcap = new_kcap
        self._w1 = w1
        if grew_w:
            self.refresh_membership(grouper, state)

    def _grow_keys(self, old_k: int, new_kcap: int) -> None:
        """Grow the per-key vectors to ``new_kcap`` ids: a copy of each,
        and a recompile of every launch that reads them."""
        kcap1 = new_kcap + 1
        self._hash_arr = _grow1(self._hash_arr, old_k, new_kcap, np.uint32)
        self._hash_ok = _grow1(self._hash_ok, old_k, new_kcap, np.bool_)
        if self.has_pane:
            self._slot_of = _grow1(self._slot_of, old_k, new_kcap, np.int32)
        if self.scheme in _RING_SCHEMES and new_kcap <= (1 << 14):
            # prefill the whole ring-hash cache at the (rare) resize so
            # steady-state feeds never touch SHA-1; for sparse key spaces
            # past 16k ids stay lazy per feed
            self._fill_hashes(np.flatnonzero(~self._hash_ok))
        # the old phantom key row (index old_k) is dropped by the [:old_k]
        # copy — it only ever holds the padding lanes' sink entries
        if self.scheme in ("dc", "wc", "fish"):
            self.trk = _grow_dev1(self.trk, old_k, kcap1, jnp.float32)
        if self.scheme == "fish":
            self.m_k = _grow_dev1(self.m_k, old_k, kcap1, jnp.int32)

    def _grow_repl(self, old_k: int, old_w: int, new_kcap: int,
                   w1: int) -> None:
        """Grow the host bitmap of seen replica pairs.  It is written in
        full as it is allocated, so no fold pays its first-touch page
        faults inside a feed."""
        out = np.full((w1, new_kcap + 1), False)
        if self._repl_seen is not None:
            # the old phantom lane and key column never hold a pair
            out[:old_w, :old_k] = self._repl_seen[:old_w, :old_k]
        self._repl_seen = out

    def _key_table_bytes(self) -> int:
        """Bytes of the per-key tables, on the device and on the host."""
        return sum(int(t.nbytes) for t in (
            self.trk, self.m_k, self._repl_seen, self._hash_arr,
            self._hash_ok, self._slot_of) if t is not None)

    def _fold_replicas(self, ws: np.ndarray, ks: np.ndarray) -> None:
        """Mark the (worker, key) pairs ``(ws[i], ks[i])`` seen, and keep
        those seen for the first time for the next ``host_sync``, under
        the span ``fused.replica_fold`` (args ``pairs``, ``new``).  Pairs
        may repeat; each is new once, so the kept list is bounded by the
        distinct pairs, never by the stream."""
        with self.tel.tracer.span("fused.replica_fold", cat="fused",
                                  pairs=int(ws.shape[0])) as fold_span:
            kcap1 = self._kcap + 1
            seen = self._repl_seen.reshape(-1)  # a view: writes land
            flat = ws.astype(np.int64) * kcap1 + ks
            fresh = flat[~seen[flat]]
            if fresh.shape[0]:
                fresh = np.unique(fresh)  # repeats within these pairs
                seen[fresh] = True
                w, k = np.divmod(fresh, kcap1)
                self._repl_new.append((w.astype(np.int32),
                                       k.astype(np.int32)))
                self._c_repl_new.add(int(fresh.shape[0]))
            fold_span.set(new=int(fresh.shape[0]))

    def refresh_membership(self, grouper, state) -> None:
        """Rebuild the device ring table + live-set arrays after a
        membership change (or worker-universe growth)."""
        ring_span = self.tel.tracer.span("fused.refresh_membership",
                                         cat="fused")
        if self.scheme in _RING_SCHEMES:
            dmax = self._dmax or max(state.busy_until.shape[0], 2)
            self._pts, self._cands = _build_ring_table(grouper.ring, dmax)
            self._pts_dev = jnp.asarray(self._pts)
            self._cands_dev = jnp.asarray(self._cands)
        if self.scheme == "fish":
            self._rank_dev = jnp.asarray(_build_rank_table(
                self._cands, state.busy_until.shape[0]))
        act = np.asarray(sorted(state.active), dtype=np.int32)
        self._act = act
        self._act_pad = np.full(self._w1, self._w1 - 1, np.int32)
        self._act_pad[:act.shape[0]] = act
        self._act_mask = np.zeros(self._w1, bool)
        self._act_mask[act] = True
        ring_span.set(live=int(act.shape[0])).done()

    # -- per-feed lifecycle -------------------------------------------------
    def begin_feed(self, grouper, state, keys_arr, values, times,
                   sink) -> None:
        self._feed_base_dispatches = self._c_dispatches.value
        with self.tel.tracer.span("fused.begin_feed", cat="fused",
                                  n=int(keys_arr.shape[0])):
            self._base = float(times[0]) if times.shape[0] else 0.0
            kmax = int(keys_arr.max()) if keys_arr.shape[0] else 0
            self._ensure_shapes(grouper, state, kmax)
            self._feed_keys = keys_arr.astype(np.int32)
            self._feed_times = times
            if self.scheme in _RING_SCHEMES:
                self._feed_hash = self._hashes(keys_arr)
            if self.has_pane:
                from ..state.window import tuple_values

                self._feed_vals = tuple_values(
                    sink.op, keys_arr, payload=values).astype(np.int32)

    def _fill_hashes(self, miss: np.ndarray) -> None:
        """Hash the keys ``miss`` into the ring-hash cache, under the span
        ``fused.hash_fill`` (arg ``keys``, 0 when every key was cached)."""
        with self.tel.tracer.span("fused.hash_fill", cat="fused",
                                  keys=int(miss.shape[0])):
            if miss.shape[0]:
                # inlined hash32 for plain int keys (same SHA-1 bucket as
                # chash.hash32): skips the per-key canonicalise/dispatch
                sha1, fb = _sha1, int.from_bytes
                self._hash_arr[miss] = np.fromiter(
                    (fb(sha1(repr(k).encode("utf-8")).digest()[:4], "big")
                     for k in miss.tolist()),
                    dtype=np.uint32, count=miss.shape[0])
                self._hash_ok[miss] = True

    def _hashes(self, keys_arr: np.ndarray) -> np.ndarray:
        ok = self._hash_ok[keys_arr]
        self._fill_hashes(np.unique(keys_arr[~ok]) if not ok.all()
                          else keys_arr[:0])
        return self._hash_arr[keys_arr]

    def _pane_slots(self, keys: np.ndarray) -> np.ndarray:
        """Each key's slot in the open pane, giving the next free slots
        to keys new to it (ascending key order), under the span
        ``fused.pane_slots`` (args ``new`` and ``slots`` in use)."""
        with self.tel.tracer.span("fused.pane_slots",
                                  cat="fused") as slot_span:
            slot = self._slot_of[keys]
            fresh = keys[:0]
            if not slot.all():
                fresh = np.unique(keys[slot == 0])
                n0 = self._n_slots
                self._n_slots = n0 + fresh.shape[0]
                if self._n_slots > self._slots:
                    raise RuntimeError(
                        f"fused pane: {self._n_slots} keys in one pane "
                        f"exceed its {self._slots} slots")
                self._slot_of[fresh] = np.arange(n0 + 1, self._n_slots + 1,
                                                 dtype=np.int32)
                slot = self._slot_of[keys]
            slot_span.set(new=int(fresh.shape[0]), slots=self._n_slots)
        return slot - 1

    def run_segment(self, grouper, state, lo: int, hi: int) -> np.ndarray:
        """One fused launch for tuples [lo, hi) of the current feed.
        Returns their absolute finish times (float64, host)."""
        tracer = self.tel.tracer
        seg_span = tracer.span("fused.segment", cat="fused",
                               scheme=self.scheme, lo=lo, hi=hi)
        prep_span = tracer.span("fused.segment.prep", cat="fused")
        m = hi - lo
        n_pad = _bucket(m)
        w1 = self._w1
        kcap1 = self._kcap + 1
        scheme = self.scheme

        keys_i = np.full(n_pad, self._kcap, np.int32)  # pad -> phantom row
        keys_i[:m] = self._feed_keys[lo:hi]
        t = np.zeros(n_pad, np.float32)
        t[:m] = self._feed_times[lo:hi] - self._base

        busy = np.zeros(w1, np.float32)
        busy[:w1 - 1] = state.busy_until - self._base
        caps = np.ones(w1, np.float32)
        caps[:w1 - 1] = state.capacities
        counts = np.zeros(w1, np.int32)
        cn = grouper.assigned_counts.shape[0]
        # the device kernel compares counts pairwise (PKG/DC argmin), never
        # absolutely — shifting all workers by the running minimum keeps
        # every comparison identical while the int64 lifetime totals stay
        # host-side, so 10⁸-tuple runs (contracts.SCALE_TARGET) never push
        # the int32 device domain past 2³¹ (ISSUE 10)
        counts_base = int(grouper.assigned_counts.min()) if cn else 0
        rebased = grouper.assigned_counts - counts_base
        if rebased.max(initial=0) + m > 2 ** 31 - 1:
            raise ValueError(
                "fused feed: per-worker count spread exceeds int32 "
                f"(max-min = {int(rebased.max(initial=0))}, feed m = {m})")
        counts[:cn] = rebased

        # host-side inputs go in as plain numpy — jit transfers them at
        # dispatch for a fraction of the cost of an eager jnp conversion
        # per array (the dominant host overhead at 16k-tuple feeds).
        # Per-key tables ride in `dev`, the donated arg: each is replaced
        # by its updated output, never read again through the old handle.
        dev = {}
        a = {"keys": keys_i, "m": np.int32(m), "t": t, "busy": busy,
             "caps": caps, "counts": counts}
        r_n = 0
        dmax = 0
        if scheme == "sg":
            a["act"] = self._act_pad
            a["a_live"] = np.int32(self._act.shape[0])
            a["rr"] = np.int32(grouper._rr)
        else:
            a["pts"] = self._pts_dev
            a["cands"] = self._cands_dev
            r_n = self._pts.shape[0]
            dmax = self._cands.shape[1]
            if kcap1 <= n_pad:  # static per sig: route keys, gather tuples
                a["hash_arr"] = self._hash_arr
            else:
                h = np.zeros(n_pad, np.uint32)
                h[:m] = self._feed_hash[lo:hi]
                a["h"] = h
        if scheme in ("dc", "wc", "fish"):
            dev["trk"] = self.trk
            a["theta"] = np.float32(self._theta(grouper))
            a["wnum"] = np.float32(grouper.num_workers)
            if scheme == "wc":
                a["act_mask"] = self._act_mask
        if scheme == "fish":
            fa = self._fish_args(grouper, lo, hi, state.offset)
            dev["m_k"] = fa.pop("m_k")
            a.update(fa)
            a["rank_of"] = self._rank_dev
        reset = False
        if self.has_pane:
            vals = np.zeros(n_pad, np.int32)
            vals[:m] = self._feed_vals[lo:hi]
            a["vals"] = vals
            slots = np.full(n_pad, self._slots, np.int32)  # pad -> phantom
            slots[:m] = self._pane_slots(self._feed_keys[lo:hi])
            a["slots"] = slots
            reset = self.pane_tab is None  # first segment of a fresh pane
            if not reset:
                dev["pane_tab"] = self.pane_tab
                dev["pane_last"] = self.pane_last
            a["seg_base"] = np.int32(state.offset + lo)

        sig = (scheme, n_pad, w1, kcap1, r_n, dmax, self.has_pane, reset,
               self.fifo_impl, self._slots + 1 if self.has_pane else 0)
        prep_span.done()
        # the one device dispatch: routing, FIFO and state-scatter run as
        # a single fused launch, so the phases share this span; the device
        # trace times them apart by their named scopes (DESIGN.md §14)
        with tracer.span("fused.segment.launch", cat="fused",
                         n_pad=n_pad) as launch_span:
            if sig not in _SEG_CACHE:
                launch_span.set(new_signature=True)  # this launch compiles
            out = _get_seg_fn(sig)(dev, a)
        self._c_dispatches.add(1)
        if scheme == "fish":
            self._c_choice_tuples.add(m)

        # device-resident state stays device-side
        if "trk" in out:
            self.trk = out["trk"]
        if "m_k" in out:
            self.m_k = out["m_k"]
        if self.has_pane:
            self.pane_tab = out["pane_tab"]
            self.pane_last = out["pane_last"]
            self.pane_fed += m

        # small per-worker vectors ride back with the launch's output fetch
        with tracer.span("fused.segment.readback", cat="fused"):
            state.busy_until[:] = self._base + np.asarray(
                out["busy"], dtype=np.float64)[:w1 - 1]
            grouper.assigned_counts[:] = counts_base + np.asarray(
                out["counts"], dtype=np.int64)[:cn]
            if scheme == "sg":
                grouper._rr = int((grouper._rr + m) % self._act.shape[0])
            elif scheme == "fish":
                est = grouper.estimator
                nw = est.backlog.shape[0]
                est.backlog[:] = np.asarray(out["ebl"],
                                            dtype=np.float64)[:nw]
                est.assigned[:] = np.asarray(out["eas"],
                                             dtype=np.float64)[:nw]
            fin = self._base + np.asarray(out["fin"], dtype=np.float64)[:m]
            workers = np.asarray(out["workers"])[:m]
        if self.has_pane:
            # the flush folds the pane's pairs once it has deduped them
            self._pane_pairs.append((workers, self._feed_keys[lo:hi]))
        else:
            self._fold_replicas(workers, self._feed_keys[lo:hi])
        if (scheme == "fish" and self.tel.enabled
                and self._fish_epochs_crossed):
            # the tracker read is a cost of tracing: its own span shows it
            with tracer.span("fish.epoch_points", cat="fish"):
                self._fish_epoch_points(grouper, state, lo, hi)
        seg_span.done()
        return fin

    def _theta(self, grouper) -> float:
        if self.scheme == "fish":
            return grouper.params.theta(grouper.num_workers)
        return grouper.theta  # dc/wc property (theta_frac / num_workers)

    def _fish_args(self, grouper, lo: int, hi: int, offset: int) -> dict:
        p = grouper.params
        est = grouper.estimator
        g0 = offset + lo
        g1 = offset + hi
        # epoch-boundary decay fires *before* the boundary tuple is
        # counted, so a segment starting exactly on a boundary decays once
        # up front
        pre = 1 if (g0 > 0 and g0 % p.epoch == 0) else 0
        c_total = (g1 - 1) // p.epoch - g0 // p.epoch + pre
        self._fish_epochs_crossed = c_total
        self._fish_epoch_idx = g1 // p.epoch
        now0 = float(self._feed_times[lo])
        do_tick = 0
        elapsed = 0.0
        if now0 - est._t_prior > est.interval:
            do_tick = 1
            elapsed = now0 - est._t_prior
            est._t_prior = now0
        w1 = self._w1
        ebl = np.zeros(w1, np.float32)
        eas = np.zeros(w1, np.float32)
        ecaps = np.ones(w1, np.float32)
        nw = est.backlog.shape[0]
        ebl[:nw] = est.backlog
        eas[:nw] = est.assigned
        ecaps[:nw] = est.capacities
        return {"m_k": self.m_k, "alpha": np.float32(p.alpha),
                "epoch": np.int32(p.epoch), "g0": np.int32(g0),
                "pre_decay": np.int32(pre),
                "c_total": np.float32(c_total),
                "d_min": np.int32(p.d_min),
                "ebl": ebl, "eas": eas, "ecaps": ecaps,
                "do_tick": np.int32(do_tick),
                "elapsed": np.float32(elapsed)}

    def _fish_epoch_points(self, grouper, state, lo: int, hi: int) -> None:
        """Per-epoch FISH timeline (telemetry-enabled only): hot-set size
        and churn computed on the device from the tracker after a segment
        that crossed one or more epoch boundaries, against the hot mask of
        the previous point kept there, plus the per-worker imbalance at
        that instant.  Two numbers are fetched, one read per segment that
        crossed an epoch, never per tuple."""
        epoch_idx = self._fish_epoch_idx
        self.tel.ctx.epoch_idx = epoch_idx
        k = self.trk.shape[0] - 1  # the phantom padding row is dropped
        prev = self._prev_hot
        if prev is None or prev.shape[0] != k:
            # key ids past the old capacity were never hot
            prev = _grow_dev1(prev, 0 if prev is None else prev.shape[0],
                              k, jnp.bool_)
        theta = grouper.params.theta(grouper.num_workers)
        size, churn, self._prev_hot = _hot_set_points(
            self.trk, prev, np.float32(theta))
        size, churn = int(size), int(churn)
        tl = self.tel.timeline
        tl.point("fish.hot_set_size", size, epoch_idx=epoch_idx)
        tl.point("fish.hot_set_churn", churn, epoch_idx=epoch_idx)
        counts = grouper.assigned_counts
        act = self._act
        if act.shape[0] and counts[act].sum() > 0:
            share = counts[act]
            tl.point("fish.worker_imbalance",
                     float(share.max() / max(share.mean(), 1e-12)),
                     epoch_idx=epoch_idx)
        self.tel.tracer.instant(
            "fish.epoch_decay", cat="fish", epoch=epoch_idx,
            crossed=int(self._fish_epochs_crossed), hot_set=size)

    # -- host sync points ---------------------------------------------------
    def flush_pane(self, sink) -> None:
        """Sync the open device pane into the host KeyedStateManager and
        drop the device tables (``merge_entries`` accumulates, so the pane
        can keep filling on device afterwards).

        Every valid tuple adds 1 to the count of exactly one (worker, key)
        entry, so the pane's live entries are the distinct pairs its
        launches recorded: the flush gathers those alone from the device
        table, at a cost that follows the tuples in the pane, not the
        table's size.  Its three steps are child spans of
        ``fused.pane_flush``: the ``.scan`` that dedupes and splits the
        pairs per worker, the ``.copy`` that gathers the entries on the
        device and fetches them, and the ``.merge`` into the host store."""
        if not self.has_pane or self.pane_fed == 0:
            return
        self._c_pane_flushes.add(1)
        tracer = self.tel.tracer
        flush_span = tracer.span("fused.pane_flush", cat="fused",
                                 pane_fed=self.pane_fed)
        scan_span = tracer.span("fused.pane_flush.scan", cat="fused")
        kcap1 = self._kcap + 1
        ws_all = np.concatenate([w for w, _ in self._pane_pairs])
        ks_all = np.concatenate([k for _, k in self._pane_pairs])
        # pairs, not flat indices, are recorded: key-capacity growth
        # mid-pane changes kcap1.  np.unique sorts worker-major with keys
        # ascending, the order the manager's consumers rely on
        flat = np.unique(ws_all.astype(np.int64) * kcap1 + ks_all)
        ws, ks = np.divmod(flat, kcap1)
        live = int(flat.shape[0])
        # one static bucket per pane size; the phantom lane and slot pad
        bucket = _bucket(self.pane_fed)
        ws_pad = np.full(bucket, self._w1 - 1, np.int32)
        ws_pad[:live] = ws
        sl_pad = np.full(bucket, self._slots, np.int32)
        sl_pad[:live] = self._slot_of[ks] - 1
        # the pane's slots are free again: reset the map where it was set
        self._slot_of[ks] = 0
        self._n_slots = 0
        starts = np.concatenate(
            [[0], np.flatnonzero(ws[1:] != ws[:-1]) + 1, [live]]).tolist()
        runs = list(zip(starts[:-1], starts[1:]))  # one run per worker
        scan_span.set(touched=int(ws_all.shape[0]), live=live).done()
        self._fold_replicas(ws, ks)
        with tracer.span("fused.pane_flush.copy", cat="fused",
                         array="entries") as copy_span:
            got, last = jax.device_get(
                (_pane_gather(self.pane_tab, ws_pad, sl_pad),
                 self.pane_last))
            self._c_pane_gathers.add(1)
            copy_span.set(bytes=got.nbytes + last.nbytes)
        with tracer.span("fused.pane_flush.merge", cat="fused",
                         entries=live):
            vs = got[:live, 0].astype(np.int64)
            cs = got[:live, 1].astype(np.int64)
            sink.feed_aggregated(
                self.pane_fed,
                [(int(ws[s]), ks[s:e], vs[s:e], cs[s:e], int(last[ws[s]]))
                 for s, e in runs])
        # None marks the pane empty — the next segment's launch starts
        # from in-jit zeros (its `reset` variant), so no buffer is
        # allocated or transferred here
        self.pane_tab = None
        self.pane_last = None
        self.pane_fed = 0
        self._pane_pairs = []
        flush_span.done()

    def host_sync(self, grouper) -> None:
        """Add the (key, worker) replica pairs first seen since the last
        sync to ``grouper.replicas``, in key-major order, the open pane's
        included: its pairs are folded here and kept for its flush.
        Called before metrics/close and before membership events."""
        if self._pane_pairs:
            self._fold_replicas(
                np.concatenate([w for w, _ in self._pane_pairs]),
                np.concatenate([k for _, k in self._pane_pairs]))
        if not self._repl_new:
            return
        self._c_host_syncs.add(1)
        with self.tel.tracer.span("fused.host_sync", cat="fused"):
            ws = np.concatenate([w for w, _ in self._repl_new])
            ks = np.concatenate([k for _, k in self._repl_new])
            order = np.lexsort((ws, ks))
            for k, w in zip(ks[order].tolist(), ws[order].tolist()):
                grouper.replicas.setdefault(k, set()).add(w)
            self._repl_new = []


# -- growth helpers (rare: each growth is a recompile boundary) -------------


def _grow1(arr, old, new, dtype):
    out = np.zeros(new, dtype)
    if arr is not None:
        out[:old] = arr[:old]
    return out


def _grow_dev1(arr, old, new1, dtype):
    out = jnp.zeros((new1,), dtype)
    return out if arr is None else out.at[:old].set(arr[:old])


def _grow_pane(arr, old_w, old_s, w1, slots):
    # pane tables are worker-major: (w1, slots + 1, 2).  The old phantom
    # slot row (index old_s) only holds the padding lanes' entries in the
    # old phantom lane: the [:old_w, :old_s] copy drops both
    out = jnp.zeros((w1, slots + 1, 2), jnp.int32)
    return out.at[:old_w, :old_s, :].set(arr[:old_w, :old_s, :])


def _grow_last(arr, old_w, w1):
    out = jnp.full((w1,), -1, jnp.int32)
    return out if arr is None else out.at[:old_w].set(arr[:old_w])


# ---------------------------------------------------------------------------
# TRACE_COUNT module-attribute compatibility (ISSUE 9 counter unification)
# ---------------------------------------------------------------------------


class _FeedFusedModule(_types.ModuleType):
    """Routes ``feed_fused.TRACE_COUNT`` reads *and* writes through the
    registry counter.  A plain module ``__getattr__`` cannot do this: the
    first ``feed_fused.TRACE_COUNT += 1`` (the ``TraceBudget`` test does
    exactly that) would create a module-dict shadow and fork the count.  A
    data descriptor on the module class intercepts both directions."""

    @property
    def TRACE_COUNT(self) -> int:
        return _TRACE_COUNTER.value

    @TRACE_COUNT.setter
    def TRACE_COUNT(self, v: int) -> None:
        _TRACE_COUNTER.set(v)


_sys.modules[__name__].__class__ = _FeedFusedModule
