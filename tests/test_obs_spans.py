"""Spans and device scopes that time the fused path from inside the
program: the pane flush's copy / scan / merge children, the window emit,
the named phases of the segment program, the mirror of every span onto
the profiler's clock, and the benchmark's readers of those spans."""

from __future__ import annotations

import glob
import re
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.data.synthetic import zipf_time_evolving
from repro.kernels import feed_fused
from repro.obs import Telemetry
from repro.state import WindowOp
from repro.topology import (Edge, SimulatorEngine, Source, Stage, Topology,
                            config_for)

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

RATE = 10_000.0
WINDOW = 4_096
FEED = 1_024
WORKERS = 16
SCHEMES = ("sg", "fg", "pkg", "dc", "wc", "fish")


def _topo(scheme, merge_scheme="fg"):
    op = WindowOp(agg="sum", value="payload", size=WINDOW)
    return Topology(
        name="spans",
        stages=(Stage("count", WORKERS, operator=op), Stage("merge", 4)),
        edges=(Edge("source", "count", config_for(scheme)),
               Edge("count", "merge", config_for(merge_scheme))))


def _session(scheme="fish", n=4 * WINDOW, merge_scheme="fg",
             telemetry=None):
    """A fused session over ``n`` tuples of 60k-key Zipf traffic, fed
    ``FEED`` tuples at a time; returns the telemetry it reported into."""
    tel = telemetry if telemetry is not None else Telemetry(enabled=True)
    keys = zipf_time_evolving(n, num_keys=60_000, z=1.1, seed=3)
    s = SimulatorEngine(mode="fused").open(
        _topo(scheme, merge_scheme), arrival_rate=RATE, telemetry=tel)
    vals = (np.arange(n) % 7 + 1).astype(np.float64)
    for b in Source(keys, arrival_rate=RATE, values=vals).iter_batches(
            batch_size=FEED):
        s.feed(b)
    s.close()
    return tel


def _within(child, parent):
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


# -- the segment program's named scopes --------------------------------------


def _scopes(scheme, has_pane):
    route = {"sg": ["route/choose"],
             "fg": ["route/ring", "route/choose"],
             "pkg": ["route/ring", "route/choose"]}.get(
        scheme, ["route/ring", "route/tracker", "route/choose"])
    tail = ["pane/scatter", "pane/last"] if has_pane else []
    return route + ["fifo"] + tail


@pytest.mark.parametrize("scheme", SCHEMES)
def test_segment_scopes_in_compiled_hlo(scheme, monkeypatch):
    # record each launch's argument shapes, then compile the same
    # signature from them and read the op metadata of the program
    seen = {}
    real = feed_fused._get_seg_fn

    def spy(sig):
        fn = real(sig)

        def call(dev, a):
            seen.setdefault(sig[6], (sig, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
                (dev, a))))
            return fn(dev, a)
        return call

    monkeypatch.setattr(feed_fused, "_get_seg_fn", spy)
    _session(scheme, n=2 * WINDOW, merge_scheme=scheme,
             telemetry=Telemetry(enabled=False))
    monkeypatch.undo()
    assert set(seen) == {True, False}  # the windowed edge and the merge
    for has_pane, (sig, (dev, a)) in seen.items():
        text = real(sig).lower(dev, a).compile().as_text()
        assert text.startswith(f"HloModule jit_seg_{scheme},")
        names = {m.group(1) for m in
                 re.finditer(r'op_name="jit\(seg_\w+\)/([^"]*)"', text)}
        for scope in _scopes(scheme, has_pane):
            assert any(n.startswith(scope + "/") for n in names), (
                scheme, has_pane, scope)


# -- the pane flush and the window emit ---------------------------------------


@pytest.fixture(scope="module")
def fish_run():
    """A traced FISH session's spans, and for each pane flush, in flush
    order, the tuples in its pane and the bytes of the entries it
    gathered and of the last-index vector it fetched."""
    flushed = []
    real_flush = feed_fused.FusedEdgeRunner.flush_pane
    real_gather = feed_fused._pane_gather

    def flush(self, sink):
        if self.has_pane and self.pane_fed:
            flushed.append({"pane_fed": self.pane_fed,
                            "last": self.pane_last.nbytes})
        return real_flush(self, sink)

    def gather(tab, ws, ks):
        out = real_gather(tab, ws, ks)
        flushed[-1]["entries"] = out.nbytes
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(feed_fused.FusedEdgeRunner, "flush_pane", flush)
        mp.setattr(feed_fused, "_pane_gather", gather)
        # the last window is still open at close()
        spans = _session("fish", n=4 * WINDOW + FEED).tracer.spans
    return spans, flushed


@pytest.fixture(scope="module")
def fish_spans(fish_run):
    return fish_run[0]


def test_pane_flush_children_nest_and_cover_the_flush(fish_run):
    fish_spans, flushed = fish_run
    flushes = sorted((s for s in fish_spans if s.name == "fused.pane_flush"),
                     key=lambda s: s.t0)
    kids = [s for s in fish_spans if s.name.startswith("fused.pane_flush.")]
    assert len(flushes) >= 4
    assert len(kids) == 3 * len(flushes)  # a scan, a copy, a merge
    for k in kids:
        assert sum(_within(k, f) for f in flushes) == 1, k.name
    covered = sum(k.t1 - k.t0 for k in kids)
    assert covered >= 0.95 * sum(f.t1 - f.t0 for f in flushes)
    assert len(flushed) == len(flushes)
    for f, got in zip(flushes, flushed):
        mine = [k for k in kids if _within(k, f)]
        copies = {k.args["array"]: k.args["bytes"] for k in mine
                  if k.name == "fused.pane_flush.copy"}
        assert copies == {"entries": got["entries"] + got["last"]}
        scan, = [k for k in mine if k.name == "fused.pane_flush.scan"]
        merge, = [k for k in mine if k.name == "fused.pane_flush.merge"]
        # every tuple of the pane is a touched pair; the dedupe keeps
        # one per live entry
        assert scan.args["touched"] == got["pane_fed"]
        assert 0 < scan.args["live"] <= scan.args["touched"]
        assert scan.args["live"] == merge.args["entries"]


def test_replica_fold_in_each_flush_and_each_paneless_launch(fish_spans):
    flushes = [s for s in fish_spans if s.name == "fused.pane_flush"]
    folds = [s for s in fish_spans if s.name == "fused.replica_fold"]
    in_flush = [f for f in folds if any(_within(f, p) for p in flushes)]
    assert len(in_flush) == len(flushes)  # one a flush, on its deduped pairs
    # outside the flush's scan and every launch's readback
    apart = [s for s in fish_spans if s.name in ("fused.pane_flush.scan",
                                                  "fused.segment.readback")]
    assert not any(_within(f, s) for f in folds for s in apart)
    # the others fold a launch of the merge edge, which has no pane
    segs = [s for s in fish_spans if s.name == "fused.segment"
            and s.args["scheme"] == "fg"]
    rest = [f for f in folds if f not in in_flush]
    assert len(rest) == len(segs)
    assert all(any(_within(f, s) for s in segs) for f in rest)
    for f in folds:
        assert 0 <= f.args["new"] <= f.args["pairs"]


def test_session_emit_once_per_operator_feed(fish_spans):
    feeds = [s for s in fish_spans if s.name == "session.feed"]
    emits = [s for s in fish_spans if s.name == "session.emit"]
    per_feed = [[e for e in emits if _within(e, f)] for f in feeds]
    assert [len(p) for p in per_feed] == [1] * len(feeds)
    for f, (e,) in zip(feeds, per_feed):
        closes = (f.args["feed_idx"] + 1) * FEED % WINDOW == 0
        assert (e.args["partials"] > 0) == closes, f.args
        assert (e.args["entries"] > 0) == closes
    # close() releases the open window through the same span
    last, = [e for e in emits if not any(_within(e, f) for f in feeds)]
    assert last.args["partials"] > 0


def test_launch_marks_its_compile_and_fish_epoch_reads(fish_spans):
    launches = [s for s in fish_spans if s.name == "fused.segment.launch"]
    new = [s for s in launches if s.args.get("new_signature")]
    assert 0 < len(new) < len(launches)
    assert all("phases" not in s.args for s in launches)
    segs = [s for s in fish_spans if s.name == "fused.segment"]
    points = [s for s in fish_spans if s.name == "fish.epoch_points"]
    assert points and all(any(_within(p, s) for s in segs) for p in points)


def test_pane_slot_hash_fill_and_key_regrow_spans(fish_spans):
    segs = [s for s in fish_spans if s.name == "fused.segment"]
    slots = [s for s in fish_spans if s.name == "fused.pane_slots"]
    # one slot assignment per launch of the windowed edge, inside it
    assert slots and all(any(_within(p, s) for s in segs) for p in slots)
    for p in slots:
        assert 0 <= p.args["new"] <= p.args["slots"] <= WINDOW
    # a flush frees the pane's slots: some launch starts from none
    assert any(p.args["new"] == p.args["slots"] > 0 for p in slots[1:])
    feeds = [s for s in fish_spans if s.name == "fused.begin_feed"]
    regrows = [s for s in fish_spans if s.name == "fused.key_regrow"]
    assert len(regrows) >= 2  # each edge sizes its key tables
    for r in regrows:
        assert r.args["bytes"] > 0 and r.args["kcap"] & (r.args["kcap"] - 1) \
            == 0
    fills = [s for s in fish_spans if s.name == "fused.hash_fill"]
    assert fills and all(any(_within(f, s) for s in feeds + regrows)
                         for f in fills)
    # every key both ring-hashing edges saw was hashed, none twice per edge
    keys = zipf_time_evolving(4 * WINDOW + FEED, num_keys=60_000, z=1.1,
                              seed=3)
    distinct = np.unique(keys).shape[0]
    assert distinct <= sum(f.args["keys"] for f in fills) <= 2 * distinct


# -- the spans on the profiler's clock ----------------------------------------


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_profiler_trace_holds_the_program_spans(tmp_path):
    tel = Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _session("sg", n=WINDOW, telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    names = {e[0] for e in _host_events(tmp_path)}
    assert {"session.feed", "fused.segment.launch",
            "fused.pane_flush.copy"} <= names
    assert {s.name for s in tel.tracer.spans} <= names


def test_span_never_done_leaves_no_annotation_open(tmp_path):
    tel = Telemetry(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        orphan = tel.tracer.span("orphan")
        del orphan
        with tel.tracer.span("after"):
            pass
    finally:
        jax.profiler.stop_trace()
    ev = {e[0]: e for e in _host_events(tmp_path)}
    assert "after" in ev
    # the dropped span's annotation closed with it, before "after" began
    assert "orphan" not in ev or ev["orphan"][2] <= ev["after"][1]
    assert [s.name for s in tel.tracer.spans] == ["after"]


# -- the benchmark's readers of the new spans --------------------------------


def _bundle():
    """Two feeds in a 10 s window, the second with a 2 s flush."""
    spans = [
        ("session.feed", 0.0, 1.0, None),
        ("session.emit", 0.8, 0.9, {"partials": 0, "entries": 0}),
        ("session.feed", 1.0, 4.0, None),
        ("fused.pane_flush", 1.5, 3.5, None),
        ("fused.pane_flush.scan", 1.5, 2.4, {"touched": 16, "live": 10}),
        ("fused.pane_flush.copy", 2.4, 3.0, {"array": "entries",
                                             "bytes": 6 * 10 ** 8 + 516}),
        ("fused.pane_flush.merge", 3.0, 3.5, {"entries": 10}),
        ("session.emit", 3.6, 3.9, {"partials": 4, "entries": 10}),
        ("fused.pane_slots", 0.1, 0.15, {"new": 7, "slots": 7}),
        ("fused.pane_slots", 1.1, 1.2, {"new": 3, "slots": 10}),
        ("fused.hash_fill", 0.05, 0.08, {"keys": 7}),
        ("fused.hash_fill", 1.05, 1.06, {"keys": 0}),
        ("fused.replica_fold", 0.5, 0.52, {"pairs": 7, "new": 7}),
        ("fused.replica_fold", 3.5, 3.53, {"pairs": 10, "new": 3}),
        ("session.emit", 11.0, 12.0, None),  # after the window
        ("fused.pane_slots", 11.1, 11.5, {"new": 0, "slots": 10}),
        ("fused.replica_fold", 11.6, 11.7, {"pairs": 4, "new": 0}),
    ]
    return {"spans": spans, "window": (0.0, 10.0)}


READINGS = {
    "pane_copy_ms_per_flush.sat": 600.0,
    "pane_copy_gb_per_s.sat": (6 * 10 ** 8 + 516) / 0.6 / 1e9,
    "pane_scan_ms_per_flush.sat": 900.0,
    "pane_merge_ms_per_flush.sat": 500.0,
    "session_emit_ms_per_feed.sat": 200.0,
    "pane_slot_ms_per_feed.sat": 75.0,
    "hash_fill_ms_per_feed.sat": 20.0,
    "replica_fold_ms_per_feed.sat": 25.0,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_reader_reads_its_spans(name):
    read = spec.metric_reader(name)
    assert read(_bundle()) == pytest.approx(READINGS[name], rel=1e-9)
    # a program without these spans (the parent of this change) reads None
    bare = {"spans": [s for s in _bundle()["spans"]
                      if s[0] in ("session.feed", "fused.pane_flush")],
            "window": (0.0, 10.0)}
    assert read(bare) is None
