"""Reference routing for FISH (paper Alg. 1-3): a plain replay.

The replay routes every tuple of the stream as the configuration states
FISH (``grouping`` and ``estimator`` in the configuration file), launch
by launch, with the launch boundaries the run had (each feed, cut at
window ends):

* Alg. 1, a dense decayed frequency tracker: every key's count decays by
  ``alpha`` before the first tuple of each ``epoch``; a tuple reads its
  key's share ``f`` and the largest share ``f_top`` once the tuples of its
  own epoch within its launch are counted (float64 here);
* Alg. 2, consistent-hash candidates with a monotone memory: a key is hot
  when ``f > theta_frac / workers``; a hot key has
  ``d0 = workers / 2^floor(log2(f_top / f))`` candidates, at least
  ``d_min``, raised to the largest ``d0`` it had in earlier launches; a
  key that is not hot has 2.  Its candidates are the first ``d`` distinct
  owners clockwise from its SHA-1 ring position (paper §5, footnote 3:
  SHA-1 truncated to 32 bits, ``virtual_nodes`` points per worker);
* Alg. 3, the wait-time estimate: each worker's estimated wait is
  ``(backlog + assigned) * capacity``, in float32; a tuple goes to the
  candidate with the least wait (the first in ring order on a tie), whose
  ``assigned`` grows by one.  At a launch's start, when more than
  ``interval`` model seconds have passed since the last estimate, the
  backlog is worked down by the time elapsed and ``assigned`` restarts.
  Capacities start at 1 and are sampled (an exponential moving average
  of the worker's true service time with Gaussian noise, drawn from the
  configuration's ``rng_seed``) once for every worker at the start and
  every ``every_tuples`` tuples after each launch.

Numbers compared:

* ``worker_count_gap``: the largest difference, over windows and workers,
  between the tuples the program's partial entries give a worker in a
  window and the tuples the replay routed there.  Where the float32
  tracker of the program and the float64 one here fall on two sides of a
  threshold, the two pick differently from then on; the loads they keep
  stay close, while a routing that ignores the waits, the candidates or
  the tracker moves thousands of tuples;
* ``route_invalid``: partial entries whose worker is not among the first
  ``d`` candidates of its key, with ``d`` the most the replay gives the
  key in that window when every threshold is taken ``1e-4`` in the key's
  favour (so that rounding cannot fail a sound run);
* ``fifo_chain_violations``, FIFO service checked without the routing.
"""

from __future__ import annotations

import hashlib
from array import array

import numpy as np

from harness.reference import fifo_chain_violations, service_time

TOL = 1e-4


def hash32(b: bytes) -> int:
    return int.from_bytes(hashlib.sha1(b).digest()[:4], "big")


def ring(workers: int, vnodes: int):
    """Sorted ring points and their owners: worker ``w``'s ``i``-th point
    is ``hash32(repr((w, i)))``, moved one step clockwise while taken."""
    owner = {}
    for w in range(workers):
        for i in range(vnodes):
            pos = hash32(repr((w, i)).encode())
            while pos in owner:
                pos = (pos + 1) % (1 << 32)
            owner[pos] = w
    pts = np.array(sorted(owner), dtype=np.uint64)
    return pts, np.array([owner[int(p)] for p in pts], dtype=np.int64)


def ring_rank(pts: np.ndarray, owners: np.ndarray, workers: int):
    """rank[r, w]: where worker ``w`` comes among the distinct owners met
    walking clockwise from ring position ``r`` (0 = the owner of ``r``)."""
    r_n = pts.shape[0]
    r = np.arange(r_n)
    dist = np.empty((r_n, workers), np.int64)
    for w in range(workers):
        mine = np.flatnonzero(owners == w)
        j = np.searchsorted(mine, r, side="left")
        nxt = np.where(j < mine.shape[0], mine[j % mine.shape[0]],
                       mine[0] + r_n)
        dist[:, w] = nxt - r
    return np.argsort(np.argsort(dist, axis=1, kind="stable"), axis=1)


def key_positions(keys: np.ndarray, pts: np.ndarray) -> np.ndarray:
    """Ring position index of each key: the first point strictly
    clockwise of the key's hash, wrapping at the end."""
    h = np.array([hash32(repr(int(k)).encode()) for k in keys.tolist()],
                 dtype=np.uint64)
    return np.searchsorted(pts, h, side="right") % pts.shape[0]


def launches(n: int, feed_ends, window: int):
    """Launch boundaries: every feed's end and every window's end."""
    cuts = {0, n}
    cuts.update(int(e) for e in feed_ends if 0 < e < n)
    cuts.update(range(window, n, window))
    return sorted(cuts)


def frequencies(keys: np.ndarray, bounds, alpha: float, epoch: int,
                num_keys: int):
    """Per tuple, its key's share ``f`` and the largest share ``f_top`` as
    Alg. 1 gives them, read once per epoch within each launch."""
    n = keys.shape[0]
    f = np.zeros(n)
    f_top = np.zeros(n)
    stored = np.zeros(num_keys + 1)  # count = stored * scale
    scale, total, top = 1.0, 0.0, 0.0  # total and top in stored units
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        pre = 1 if lo > 0 and lo % epoch == 0 else 0
        first = lo // epoch
        cuts = [lo] + [e * epoch for e in range(first + 1,
                                                (hi - 1) // epoch + 1)]
        cuts.append(hi)
        for j, (a, b) in enumerate(zip(cuts[:-1], cuts[1:])):
            if j > 0 or pre:
                scale *= alpha
                if scale < 1e-200:
                    stored *= scale
                    total *= scale
                    top *= scale
                    scale = 1.0
            k = keys[a:b]
            np.add.at(stored, k, 1.0 / scale)
            total += (b - a) / scale
            top = max(top, float(stored[k].max()))
            f[a:b] = stored[k] / total
            f_top[a:b] = top / total
    return f, f_top


def candidates(keys, f, f_top, bounds, g, workers: int, tol: float = 0.0):
    """Per tuple, how many candidates Alg. 2 gives its key; with ``tol``
    every threshold is moved by that share in the key's favour."""
    theta = g["theta_frac"] / workers * (1.0 - tol)
    hot = (f > theta) & (f > 0.0) & (f_top > 0.0)
    ratio = np.maximum(f_top / np.maximum(f, 1e-30) * (1.0 - tol), 1.0)
    index = np.clip(np.floor(np.log2(ratio)), 0.0, 30.0)
    d0 = np.clip(np.floor(workers / np.exp2(index)), g["d_min"],
                 workers).astype(np.int64)
    d = np.full(keys.shape[0], 2, np.int64)
    memory = np.zeros(int(keys.max(initial=0)) + 1, np.int64)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        k, h = keys[lo:hi], hot[lo:hi]
        prev = memory[k]
        d[lo:hi] = np.where(h, np.maximum(d0[lo:hi], prev), 2)
        np.maximum.at(memory, k[h], np.maximum(prev, d0[lo:hi])[h])
    return d


def _f32(x: float, _buf=array("f", [0.0])) -> float:
    _buf[0] = x
    return _buf[0]


def choose(rows, pos, d, t, bounds, config, rnd=_f32) -> np.ndarray:
    """Alg. 3: per tuple, the worker with the least estimated wait among
    its first ``d`` candidates.  ``rnd`` rounds every estimate to the
    precision the configuration states (float32)."""
    g, s = config["grouping"], config["estimator"]
    w_n = config["workers"]
    svc = service_time(config)
    ema = s["ema"]
    caps = [ema * svc + (1.0 - ema) * 1.0] * w_n
    rng = np.random.default_rng(s["rng_seed"])
    backlog = [0.0] * w_n
    asn = [0.0] * w_n
    t_prior = 0.0
    out = np.empty(t.shape[0], np.int64)
    rows_l = rows.tolist()
    pos_l = pos.tolist()
    d_l = d.tolist()
    every = s["every_tuples"]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        ec = [rnd(c) for c in caps]
        now0 = float(t[lo])
        if now0 - t_prior > g["interval"]:
            elapsed = rnd(now0 - t_prior)
            for w in range(w_n):
                work = rnd(rnd(backlog[w] + asn[w]) * ec[w])
                backlog[w] = (rnd(rnd(work - elapsed) / ec[w])
                              if work > elapsed else 0.0)
                asn[w] = 0.0
            t_prior = now0
        wait = [rnd(rnd(backlog[w] + asn[w]) * ec[w]) for w in range(w_n)]
        for i in range(lo, hi):
            row = rows_l[pos_l[i]]
            dd = d_l[i]
            if dd == 2:
                a, b = row[0], row[1]
                w = a if wait[a] <= wait[b] else b
            else:
                w = min(row[:dd], key=wait.__getitem__)
            out[i] = w
            asn[w] += 1.0
            wait[w] = rnd(rnd(backlog[w] + asn[w]) * ec[w])
        for _ in range(lo // every + 1, hi // every + 1):
            for w in range(w_n):
                noisy = svc * (1.0 + rng.normal(0.0, s["noise"]))
                caps[w] = ema * max(noisy, 1e-12) + (1.0 - ema) * caps[w]
    return out


class Replay:
    """The replay of one stream: ``workers`` (per tuple), ``d_hi`` (per
    tuple, the candidates with every threshold in the key's favour), and
    the ring's ``rank`` and each tuple's ring position ``pos``."""

    def __init__(self, stream, config, rnd=_f32):
        g = config["grouping"]
        w_n = config["workers"]
        keys = stream["keys"].astype(np.int64)
        n = keys.shape[0]
        self.bounds = launches(n, stream["feed_ends"], config["window"])
        pts, owners = ring(w_n, g["virtual_nodes"])
        self.pts = pts
        self.rank = ring_rank(pts, owners, w_n)
        rows = np.argsort(self.rank, axis=1)
        uk, inv = np.unique(keys, return_inverse=True)
        self.pos = key_positions(uk, pts)[inv]
        f, f_top = frequencies(keys, self.bounds, g["alpha"], g["epoch"],
                               int(keys.max(initial=0)) + 1)
        d = candidates(keys, f, f_top, self.bounds, g, w_n)
        self.d_hi = candidates(keys, f, f_top, self.bounds, g, w_n, TOL)
        self.workers = choose(rows, self.pos, d, stream["t"], self.bounds,
                              config, rnd)


def worker_count_gap(out, workers: np.ndarray, window: int,
                     w_n: int) -> int:
    gap = 0
    n = workers.shape[0]
    span = max(w_n, int(out.p_worker.max(initial=0)) + 1)
    for s in range(0, n, window):
        m = out.p_window == s
        prog = np.bincount(out.p_worker[m], weights=out.p_count[m],
                           minlength=span)
        ref = np.bincount(workers[s:s + window], minlength=span)
        gap = max(gap, int(np.abs(prog - ref).max()))
    return gap


def route_invalid(out, keys, rep: Replay, window: int, w_n: int) -> int:
    bad = 0
    for s in np.unique(out.p_window).tolist():
        m = out.p_window == s
        pk, pw = out.p_key[m], out.p_worker[m]
        uk, inv = np.unique(keys[s:s + window].astype(np.int64),
                            return_inverse=True)
        if not uk.shape[0]:
            bad += int(m.sum())
            continue
        dmax = np.zeros(uk.shape[0], np.int64)
        np.maximum.at(dmax, inv, rep.d_hi[s:s + window])
        pos = np.zeros(uk.shape[0], np.int64)
        pos[inv] = rep.pos[s:s + window]
        j = np.minimum(np.searchsorted(uk, pk), uk.shape[0] - 1)
        ok = (uk[j] == pk) & (pw >= 0) & (pw < w_n)
        r = rep.rank[pos[j[ok]], pw[ok]]
        bad += int(np.count_nonzero(~ok)
                   + np.count_nonzero(r >= dmax[j[ok]]))
    return bad


def check(stream, out, config, rnd=_f32):
    w_n = config["workers"]
    keys = stream["keys"]
    n = keys.shape[0]
    chain = (fifo_chain_violations(stream["t"], out.latency[:n],
                                   service_time(config))
             if out.latency.shape[0] == n else n)
    rep = Replay(stream, config, rnd)
    return {"fifo_chain_violations": chain,
            "route_invalid": route_invalid(out, keys, rep, config["window"],
                                           w_n),
            "worker_count_gap": worker_count_gap(out, rep.workers,
                                                 config["window"], w_n)}
