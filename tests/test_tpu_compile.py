"""Ahead-of-time compiles for a TPU v5e chip that is described, not attached.

The fused segment function at the benchmark's shapes (129 worker lanes x
16,384 tuples, associative-scan FIFO, a pane of 65,536 slots) at 2^20+1
and at 2^24+1 key rows (the 10^7-key ZF cell), FISH's with its choice
kernel compiled as the chip runs it, the pane flush's gather from its
table, and FISH's choice kernel at 16,384 tuples x 128 lanes.  What the chip's
compiler refuses (scoped-VMEM overflow, a program past 16 GB) fails here
at no chip time.  Nothing runs, so this says nothing about results.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import feed_fused
from repro.kernels import fish_choose as _fish_choose
from repro.kernels import ops as _ops

HBM_BYTES = 16 * 10 ** 9  # one v5e chip
WORKERS = 128
KEY_CAPS = (1 << 20, 1 << 24)
FEED = 16_384
SLOTS = 4 * FEED  # a 65,536-tuple window's pane
RING_POINTS = 64 * WORKERS  # 64 virtual nodes per worker (core/baselines)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _segment_specs(spec, scheme, n_pad, w1, kcap1, r_n, dmax, reset, s1):
    """(dev, a) argument shapes of one fused segment launch, laid out as
    :meth:`FusedEdgeRunner.run_segment` builds them (with a pane)."""
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    a = {"keys": spec((n_pad,), i32), "m": spec((), i32),
         "t": spec((n_pad,), f32), "busy": spec((w1,), f32),
         "caps": spec((w1,), f32), "counts": spec((w1,), i32),
         "vals": spec((n_pad,), i32), "slots": spec((n_pad,), i32),
         "seg_base": spec((), i32)}
    dev = {}
    if scheme == "sg":
        a.update(act=spec((w1,), i32), a_live=spec((), i32),
                 rr=spec((), i32))
    else:
        a.update(pts=spec((r_n,), u32), cands=spec((r_n, dmax), i32),
                 h=spec((n_pad,), u32))
    if scheme == "fish":
        dev.update(trk=spec((kcap1,), f32), m_k=spec((kcap1,), i32))
        a.update(theta=spec((), f32), wnum=spec((), f32),
                 alpha=spec((), f32), epoch=spec((), i32),
                 g0=spec((), i32), pre_decay=spec((), i32),
                 c_total=spec((), f32), d_min=spec((), i32),
                 ebl=spec((w1,), f32), eas=spec((w1,), f32),
                 ecaps=spec((w1,), f32), do_tick=spec((), i32),
                 elapsed=spec((), f32),
                 rank_of=spec((r_n, -(-(w1 - 1) // 128) * 128), i32))
    if not reset:
        dev.update(pane_tab=spec((w1, s1, 2), i32),
                   pane_last=spec((w1,), i32))
    return dev, a


def _array_dims(hlo):
    """The dimensions of every array shape written in an HLO text."""
    return re.findall(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]+)\]", hlo)


def _elements(dims):
    n = 1
    for d in dims.split(","):
        n *= int(d)
    return n


def _sig(scheme, kcap, reset):
    w1 = WORKERS + 1
    r_n = 0 if scheme == "sg" else RING_POINTS
    dmax = {"sg": 0, "pkg": 2}.get(scheme, WORKERS)
    return (scheme, FEED, w1, kcap + 1, r_n, dmax, True, reset, "assoc",
            SLOTS + 1)


@pytest.mark.parametrize("kcap", KEY_CAPS, ids=("2p20", "2p24"))
@pytest.mark.parametrize("reset", (True, False), ids=("fresh", "continuing"))
@pytest.mark.parametrize("scheme", ("sg", "pkg", "fish"))
def test_segment_compiles_for_v5e(one_chip, scheme, reset, kcap,
                                  monkeypatch):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # this process's backend is the CPU, where the Pallas kernels trace
    # in interpret mode: trace them for the chip, in a fresh cache
    monkeypatch.setattr(_ops, "_interpret", lambda: False)
    monkeypatch.setattr(feed_fused, "_SEG_CACHE", {})
    sig = _sig(scheme, kcap, reset)
    w1, kcap1, s1 = sig[2], sig[3], sig[-1]
    dev, a = _segment_specs(spec, *sig[:6], reset, s1)
    exe = feed_fused._get_seg_fn(sig).lower(dev, a).compile()
    # FISH's choice runs as the Pallas kernel inside its segment
    assert ("tpu_custom_call" in exe.as_text()) == (scheme == "fish")
    ma = exe.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert live < HBM_BYTES
    # the per-key tables are donated: a continuing pane updates in place
    assert ma.alias_size_in_bytes >= (0 if reset else w1 * s1 * 2 * 4)
    if kcap == KEY_CAPS[0] and not reset:
        # no (key, worker) matrix is on the device, nor a flat copy of
        # one, and so none of the loops that relaid such a matrix out
        assert not [d for d in _array_dims(exe.as_text())
                    if _elements(d) == kcap1 * w1]
        assert ma.temp_size_in_bytes < 100 * 10 ** 6


@pytest.mark.parametrize("kcap", KEY_CAPS, ids=("2p20", "2p24"))
def test_pane_gather_reads_the_segment_layout_for_v5e(one_chip, kcap):
    # the flush gathers from the table the segment wrote: the gather must
    # take it in the layout the segment leaves it in, or the chip copies
    # the whole table to relayout it before every flush
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    sig = _sig("sg", kcap, True)
    w1, s1 = sig[2], sig[-1]
    dev, a = _segment_specs(spec, *sig[:6], True, s1)
    seg = feed_fused._get_seg_fn(sig).lower(dev, a).compile()
    bucket = 4 * FEED
    gather = feed_fused._pane_gather.lower(
        spec((w1, s1, 2), jnp.int32), spec((bucket,), jnp.int32),
        spec((bucket,), jnp.int32)).compile()
    table = re.compile(rf"s32\[{w1},{s1},2\]\{{[^}}]*\}}")

    def layouts(exe):
        head = exe.as_text().split("\n", 1)[0]
        return table.findall(head.split("entry_computation_layout=", 1)[1])

    written, = layouts(seg)  # the fresh pane's table is an output only
    read, = layouts(gather)
    assert read == written
    ma = gather.memory_analysis()
    assert ma.output_size_in_bytes == bucket * 2 * 4
    assert ma.temp_size_in_bytes < w1 * s1  # no copy of the table


@pytest.mark.parametrize("kernel", ("fish_choose",))
def test_stream_kernel_compiles_for_v5e(one_chip, kernel):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lane = spec((WORKERS,), jnp.float32)
    fn, args = {
        "fish_choose": (_fish_choose.fish_choose,
                        (spec((FEED, WORKERS), jnp.int32), lane, lane,
                         lane)),
    }[kernel]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
