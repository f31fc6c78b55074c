"""The benchmark harness on the CPU: metric arithmetic, trace reduction,
byte counts, loading by name, the generator, and the refusal without a
TPU.  Run with ``python -m pytest bench/tests``."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from harness import devtrace, loops, roofline, spec, stream
from harness.loops import FeedLog

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURES = BENCH / "fixtures"


# -- closed and open loop arithmetic -------------------------------------------

def _closed_logs(durations, cycle_every=4, n=100):
    logs, t = [], 0.0
    for i, d in enumerate(durations):
        logs.append(FeedLog(n, t, t + d, (i + 1) % cycle_every == 0))
        t += d
    return logs


def test_closed_rate_counts_whole_cycles_only():
    logs = _closed_logs([1.0] * 10)  # cycles end at feeds 4 and 8
    tuples, secs, cycles = loops.closed_rate(logs, 0.0)
    assert (tuples, secs, cycles) == (800, 8.0, 2)


def test_closed_rate_sees_a_stall():
    calm = loops.closed_rate(_closed_logs([1.0] * 8), 0.0)
    stalled = loops.closed_rate(_closed_logs([1.0, 1.0, 1.0, 3.0] * 2), 0.0)
    assert stalled[0] == calm[0]
    assert stalled[0] / stalled[1] < calm[0] / calm[1]


def test_run_closed_stops_at_the_first_cycle_end_past_the_window():
    now = [0.0]

    def clock():
        return now[0]

    def feed(b):
        now[0] += 1.0

    batches = [([0] * 10, (i + 1) % 4 == 0) for i in range(40)]
    logs, t0 = loops.run_closed(feed, batches, 5.0, clock=clock)
    assert len(logs) == 8 and logs[-1].cycle_end


def _open_run(stall_at=None, stall=0.0, seconds=10.0, rate=100.0, b=10):
    """Drive run_open on a fake clock: every feed takes 0.01 s, and the
    feed at index ``stall_at`` takes ``stall`` s more."""
    now = [0.0]

    def clock():
        return now[0]

    def sleep(s):
        now[0] += s

    k = [0]

    def feed(batch):
        now[0] += 0.01 + (stall if k[0] == stall_at else 0.0)
        k[0] += 1

    batches = [([0] * b, False)] * 1000
    logs, t0, t_end = loops.run_open(feed, batches, rate, seconds,
                                     clock=clock, sleep=sleep)
    return loops.open_latencies(logs, rate, t0, t_end), logs


def test_open_latency_of_a_calm_run_is_batching_plus_service():
    lat, _ = _open_run()
    # the first tuple of a 10-tuple feed waits 9/rate for the last one
    assert lat.shape[0] == 1001
    assert np.isclose(lat.max(), 0.09 + 0.01) or lat.max() <= 0.1 + 1e-9


def test_a_stall_inside_the_window_moves_p99():
    calm, _ = _open_run()
    stalled, logs = _open_run(stall_at=30, stall=2.0)
    assert np.percentile(stalled, 99) > np.percentile(calm, 99) + 1.0
    # the loop sent the backlog late, not never
    assert any(g.start - g.due > 1.0 for g in logs)


def test_unfinished_tuples_are_censored_at_the_window_end():
    # a stall that outlasts the window: tuples still queued count their
    # wait until the window's end, not nothing
    lat, logs = _open_run(stall_at=95, stall=30.0)
    assert lat.shape[0] == 1001
    assert logs[-1].end > 10.0
    assert np.all(lat <= 10.0 + 1e-9)
    assert lat[960] == pytest.approx(10.0 - 9.6)


def test_open_latencies_count_tuples_of_a_feed_not_yet_due():
    logs = [FeedLog(10, 0.09, 0.1, False, 0.09)]
    lat = loops.open_latencies(logs, 100.0, 0.0, 0.15)
    assert lat.shape[0] == 16
    assert lat[10] == pytest.approx(0.05)  # created at 0.10, unsent


# -- trace reduction ------------------------------------------------------------

def test_union_busy_and_gaps():
    ev = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("c", 3.0, 4.0),
          ("d", 3.2, 3.5)]
    assert devtrace.busy_seconds(ev, 0.0, 5.0) == pytest.approx(3.0)
    assert devtrace.busy_seconds(ev, 1.5, 3.5) == pytest.approx(1.0)
    assert devtrace.idle_gaps(ev, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    spans = [("host.outer", 1.0, 6.0), ("host.inner", 1.9, 3.1)]
    assert devtrace.label_gaps([(2.0, 3.0), (4.0, 5.0)], spans, 0.0) == [
        ["host.inner", 1.0], ["host.outer", 1.0]]
    assert devtrace.top_ops(ev, 0.0, 5.0)[0] == ["b", 1.5]
    assert devtrace.module_seconds(
        [("jit_seg", 0.0, 1.0), ("jit_other", 1.0, 2.0)], "jit_seg",
        0.0, 5.0) == 1.0


@pytest.fixture(scope="module")
def fixture_trace():
    path = FIXTURES / "sg_small.xplane.pb"
    if not path.exists():
        pytest.fail("bench/fixtures/sg_small.xplane.pb is missing")
    meta = json.loads((FIXTURES / "sg_small.spans.json").read_text())
    return devtrace.extract(str(path)), meta


def test_fixture_trace_reduces(fixture_trace):
    ex, meta = fixture_trace
    assert len(ex["anchors"]) == 1 and len(ex["devices"]) == 1
    dev = next(iter(ex["devices"].values()))
    an = ex["anchors"][0]
    offset = sum(meta["anchor"]) / 2 - (an[0] + an[1]) / 2
    w0, w1 = (t - offset for t in meta["window"])
    busy = devtrace.busy_seconds(dev["ops"], w0, w1)
    assert 0.0 < busy < w1 - w0
    seg = devtrace.module_seconds(dev["modules"], "jit_seg", w0, w1)
    # a program spans its operations and the short gaps between them
    assert busy <= seg + 1e-9 and seg < w1 - w0
    # every traced launch falls inside a host span of the same feed
    spans = [tuple(s[:3]) for s in meta["spans"]]
    launches = [s for s in spans if s[0] == "fused.segment"]
    mods = [m for m in dev["modules"] if m[0].startswith("jit_seg")
            and w0 <= m[1] <= w1]
    assert len(mods) == len(launches)
    gaps = devtrace.idle_gaps(dev["ops"], w0, w1)
    labels = devtrace.label_gaps(gaps, spans, offset)
    assert labels and all(isinstance(n, str) and s > 0 for n, s in labels)


# -- roofline bytes --------------------------------------------------------------

def test_segment_bytes():
    assert roofline.segment_bytes("sg", 1000, 129, True) == (
        (4 + 4 + 4 + 2 + 4 + 16) * 1000 + 20 * 129)
    assert roofline.segment_bytes("fg", 1000, 33, False) == (
        (4 + 4 + 4 + 2 + 4) * 1000 + 20 * 33)
    assert roofline.segment_bytes("fish", 1, 129, True) == (
        4 + 4 + 4 + 2 + 4 + 4 + 16 + 16 + 40 * 129)


# -- everything by name ----------------------------------------------------------

def test_every_cell_loads_by_name_and_metrics_match_their_cells():
    bm = spec.load_benchmark(ROOT)
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("closed", "open")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        reader = spec.metric_reader(m["name"], ROOT)
        assert callable(reader)
        for cell in m.get("workloads", []):
            e2e_here, _ = spec.cell_metrics(bm, cell)
            assert m["moves"] in {x["name"] for x in e2e_here}


@pytest.mark.parametrize("scheme", ["sg", "fish"])
def test_a_new_config_mix_and_metric_need_only_new_files(tiny_root, scheme,
                                                         capsys):
    # an open-loop cell, its end-to-end metrics and a per-layer metric,
    # added as files and entries only, load by name and run end to end
    import run_cell

    bm = json.loads((tiny_root / "BENCHMARK.json").read_text())
    src = {"sg": "zf-sg-128w", "fish": "zf-fish-128w"}[scheme]
    (tiny_root / "bench" / "configs" / "extra.json").write_text(
        (tiny_root / "bench" / "configs" / f"{src}.json").read_text()
        .replace(f'"{src}"', '"extra"'))
    (tiny_root / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "open", "feed_tuples": 256, "rate_tuples_per_s": 4000.0,
         "warmup_tuples": 4096, "why": "x"}))
    (tiny_root / "bench" / "metrics" / "answer.py").write_text(
        "def read(b):\n    return 42.0\n")
    bm["configs"].append({"name": "extra", "source": "x",
                          "file": "bench/configs/extra.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "extra.burst", "config": "extra",
                            "traffic": "burst", "chips": 1, "why": "x"})
    for q in ("p50", "p99"):
        bm["end_to_end"].append({"name": f"tuple_latency_{q}_ms",
                                 "unit": "ms", "better": "lower",
                                 "bound": 0.1, "source": "host_clock",
                                 "workloads": ["extra.burst"]})
    bm["per_layer"].append({"name": "answer.open", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "x", "moves": "tuple_latency_p99_ms",
                            "workloads": ["extra.burst"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = spec.load_cell("extra.burst", tiny_root)
    assert cell.traffic["feed_tuples"] == 256
    assert "answer.open" in {m["name"] for m in cell.per_layer}
    assert spec.metric_reader("answer.open", tiny_root)({}) == 42.0
    rc = run_cell.main(["--workload", "extra.burst", "--seed", "2147483659",
                        "--seconds", "1", "--trace", "0"], root=tiny_root,
                       require_accelerator=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"], res["checks"]
    assert set(res["metrics"]) == {"tuple_latency_p50_ms",
                                   "tuple_latency_p99_ms", "setup_s"}


# -- the generator ----------------------------------------------------------------

ZF = {"num_keys": 5000, "zipf_z": 1.2, "flip_at": 0.8, "flip_head": 100,
      "flip_period": 4096, "payload_min": 1, "payload_max": 99,
      "model_rate": 100000}


def test_generator_is_seeded_and_prefix_stable():
    big = 2 ** 31 + 12345

    def draw(n, seed, parts=1):
        s = stream.ZFStream(ZF, seed)
        out = [s.take(n // parts) for _ in range(parts)]
        return [np.concatenate(c) for c in zip(*out)]

    a, av, at = draw(10000, big)
    b, bv, _ = draw(10000, big, parts=4)
    c, cv, _ = draw(20000, big)
    d, _, _ = draw(10000, big + 1)
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 5000
    assert np.array_equal(a, b) and np.array_equal(a, c[:10000])
    assert np.array_equal(av, bv) and np.array_equal(av, cv[:10000])
    assert not np.array_equal(a, d)
    assert av.min() >= 1 and av.max() <= 99
    assert at[1] == pytest.approx(1e-5)
    s = stream.ZFStream(ZF, big)
    s.take(300)
    s.take(700)
    fed = s.fed()
    assert np.array_equal(fed["keys"], a[:1000])
    assert np.array_equal(fed["values"], av[:1000])


def test_generator_flips_the_hot_head_in_every_period():
    k, _, _ = stream.ZFStream(dict(ZF, flip_period=10000), 3).take(20000)
    for base in (0, 10000):
        assert np.bincount(k[base:base + 8000]).argmax() == 0
        assert np.bincount(k[base + 8000:base + 10000]).argmax() == 99


def test_feed_sizes_never_cross_a_window():
    import run_cell

    gen = run_cell.feed_sizes(1500, 4096)
    sizes = [next(gen) for _ in range(6)]
    assert sizes == [1500, 1500, 1096, 1500, 1500, 1096]
    gen = run_cell.feed_sizes(16384, 65536)
    assert [next(gen) for _ in range(6)] == [16384] * 6


# -- no TPU, no result -------------------------------------------------------------

def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         "zf-sg-128w.sat16k", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
