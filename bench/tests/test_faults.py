"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip, drives the rest of a run
at a small size on the CPU, and plants one fault in the program where
an answer is produced: a window value altered as the device pane
reaches the host, a partial entry moved to another worker or dropped,
a finish time altered as the launch returns it, or FISH's routing
taking the first candidate, the candidate with the longest estimated
wait, or two candidates for every key (the tracker ignored).  (The other faults a
check must catch, a training step that returns its state unchanged,
half a batch left out, a missing exchange between chips, do not exist in
a stream cell.)"""

from __future__ import annotations

import json

import numpy as np
import pytest

import jax.numpy as jnp

import run_cell
from harness import spec
from repro.kernels import feed_fused
from repro.kernels.feed_fused import FusedEdgeRunner
from repro.state.window import KeyedStateManager

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _run(root, cell, capsys):
    rc = run_cell.main(["--workload", cell, "--seed", "4294967311",
                        "--seconds", "1", "--trace", "0"], root=root,
                       require_accelerator=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell, capsys):
    res = _run(tiny_root, cell, capsys)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def _patch_pane(monkeypatch, edit):
    orig = KeyedStateManager.feed_aggregated
    hit = {"done": False}

    def faulty(self, n_tuples, entries):
        if not hit["done"] and self.idx >= 4096 and entries:
            entries = edit(list(entries))
            hit["done"] = True
        return orig(self, n_tuples, entries)

    monkeypatch.setattr(KeyedStateManager, "feed_aggregated", faulty)


def _value_plus_one(entries):
    w, ks, vs, cs, last = entries[0]
    vs = vs.copy()
    vs[0] += 1
    return [(w, ks, vs, cs, last)] + entries[1:]


def _worker_moved(entries):
    # the busiest key of worker 0's entries goes to the farthest worker
    w, ks, vs, cs, last = entries[0]
    far = max(e[0] for e in entries)
    return [(w, ks[1:], vs[1:], cs[1:], last),
            (far + 3, ks[:1], vs[:1], cs[:1], last)] + entries[1:]


def _entry_dropped(entries):
    w, ks, vs, cs, last = entries[0]
    return [(w, ks[1:], vs[1:], cs[1:], last)] + entries[1:]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("edit", [_value_plus_one, _worker_moved,
                                  _entry_dropped])
def test_altered_window_state_is_caught(tiny_root, cell, edit, monkeypatch,
                                        capsys):
    _patch_pane(monkeypatch, edit)
    res = _run(tiny_root, cell, capsys)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_finish_time_is_caught(tiny_root, cell, monkeypatch, capsys):
    orig = FusedEdgeRunner.run_segment
    hit = {"n": 0}

    def faulty(self, grouper, state, lo, hi):
        fin = orig(self, grouper, state, lo, hi)
        hit["n"] += 1
        if hit["n"] == 7 and self.has_pane:
            fin = fin.copy()
            fin[fin.shape[0] // 2] += 0.37 * float(np.mean(state.capacities))
        return fin

    monkeypatch.setattr(FusedEdgeRunner, "run_segment", faulty)
    res = _run(tiny_root, cell, capsys)
    assert not res["correct"], res["checks"]


def _first_candidate(route, a, row):
    out = route(a, row)
    workers = jnp.where(a["valid"], row[:, 0], a["phantom_w"])
    return out[:1] + (workers,) + out[2:]


def _longest_wait(route, a, row):
    # negated capacities turn the least-wait argmin into the most-wait one
    return route(dict(a, ecaps=-a["ecaps"]), row)


def _tracker_ignored(route, a, row):
    return route(dict(a, theta=jnp.float32(jnp.inf)), row)


@pytest.mark.parametrize("fault", [_first_candidate, _longest_wait,
                                   _tracker_ignored])
def test_fish_routing_fault_is_caught(tiny_root, fault, monkeypatch,
                                      capsys):
    orig = feed_fused._route_fish
    monkeypatch.setattr(feed_fused, "_route_fish",
                        lambda a, row: fault(orig, a, row))
    monkeypatch.setattr(feed_fused, "_SEG_CACHE", {})
    res = _run(tiny_root, "zf-fish-128w.sat16k", capsys)
    assert not res["correct"], res["checks"]
    assert res["checks"]["worker_count_gap"]["value"] > \
        res["checks"]["worker_count_gap"]["limit"]
