"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a summary footer).

    PYTHONPATH=src python -m benchmarks.run [--only fig9] [--trace DIR]
    PYTHONPATH=src python -m benchmarks.run --sanitize

``--trace DIR`` records one Perfetto-loadable Chrome trace-event file per
benchmark module (``DIR/<module>.trace.json``) by enabling process-wide
telemetry around each ``run()``.  A module that fails still leaves a
*valid* sealed trace (stamped ``aborted``) — never truncated JSON.

``--sanitize`` skips the benchmarks and runs the ISSUE-10 differential
sanitizer instead (:mod:`repro.analysis.sanitize`): one fused-engine and
one serving-engine session, each run twice with the same seed under
``np.seterr(all="raise")`` + ``jax_debug_nans``, the two
``TopologyReport``\\ s diffed field-by-field bit-for-bit.  Exit 1 on any
divergence or numeric fault — the dynamic gate CI pairs with the static
``repro.analysis`` scan.
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

from .common import Reporter


def _sanitize_targets():
    """(name, factory) pairs for the sanitizer: each factory builds a fresh
    engine + topology + source and returns a TopologyReport.  One fused
    simulator session and one serving session — the two engines whose
    device/tick paths the static rules cannot fully see."""
    import numpy as np

    from repro.data.synthetic import zipf_time_evolving
    from repro.topology import (Edge, ServingTopologyEngine, SimulatorEngine,
                                Source, Stage, Topology, config_for)

    def topo(name):
        return Topology(name=name,
                        stages=(Stage("worker", parallelism=32),),
                        edges=(Edge("source", "worker", config_for("pkg")),))

    def keys():
        return np.asarray(zipf_time_evolving(
            20_000, num_keys=2_000, z=1.2, flip_head=600, seed=7))

    def fused():
        return SimulatorEngine(mode="fused", seed=3).run(
            topo("sanitize-fused"), Source(keys(), arrival_rate=20_000.0))

    def serving():
        return ServingTopologyEngine(max_requests=64).run(
            topo("sanitize-serving"), Source(keys(), arrival_rate=20_000.0))

    return [("fused", fused), ("serving", serving)]


def _sanitize() -> int:
    from repro.analysis.sanitize import double_run

    failed = 0
    for name, factory in _sanitize_targets():
        try:
            _, _, divergences = double_run(factory)
        except Exception as e:
            if not isinstance(e, FloatingPointError):
                traceback.print_exc()
            print(f"sanitize[{name}]: FAIL — "
                  f"{type(e).__name__} under strict numerics: {e}")
            failed += 1
            continue
        if divergences:
            print(f"sanitize[{name}]: FAIL — same-seed runs diverge:")
            for d in divergences:
                print(f"  {d}")
            failed += 1
        else:
            print(f"sanitize[{name}]: PASS — double run bit-identical")
    return 1 if failed else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module name")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="record a Chrome trace per module into DIR")
    ap.add_argument("--sanitize", action="store_true",
                    help="run the differential sanitizer (same-seed double "
                    "run under strict numerics) instead of the benchmarks")
    args = ap.parse_args()

    from repro.compile_cache import configure

    configure()
    if args.sanitize:
        sys.exit(_sanitize())

    from . import (bench_breakdown, bench_chash, bench_deploy,
                   bench_feed_fused, bench_grouping, bench_latency,
                   bench_memory, bench_moe, bench_motivating, bench_params,
                   bench_scenarios, bench_session, bench_slo, bench_state,
                   bench_topology, roofline)

    modules = [
        ("bench_motivating", bench_motivating),   # Figs. 2-3
        ("bench_grouping", bench_grouping),       # batched engine tps (ISSUE 1)
        ("bench_latency", bench_latency),         # Figs. 9-10
        ("bench_memory", bench_memory),           # Fig. 11
        ("bench_params", bench_params),           # Figs. 12-13
        ("bench_breakdown", bench_breakdown),     # Figs. 14-16
        ("bench_chash", bench_chash),             # Fig. 17
        ("bench_scenarios", bench_scenarios),     # RQ4 scenario suite (ISSUE 2)
        ("bench_topology", bench_topology),       # multi-stage DAGs (ISSUE 3)
        ("bench_state", bench_state),             # keyed operator state (ISSUE 4)
        ("bench_session", bench_session),         # streaming sessions (ISSUE 5)
        ("bench_feed_fused", bench_feed_fused),   # fused device feeds (ISSUE 6)
        ("bench_slo", bench_slo),                 # open-loop SLO sweep (ISSUE 8)
        ("bench_deploy", bench_deploy),           # Figs. 18-20
        ("bench_moe", bench_moe),                 # beyond-paper MoE routing
        ("roofline", roofline),                   # §Roofline table
    ]

    rep = Reporter()
    for name, mod in modules:
        if args.only and args.only not in name:
            continue
        writer = None
        if args.trace:
            from repro.obs import telemetry
            from repro.obs.export import TraceWriter

            telemetry.enable(label=name)
            writer = TraceWriter(
                os.path.join(args.trace, f"{name}.trace.json"))
            rep.attach_trace(writer)
        try:
            mod.run(rep)
            if writer is not None:
                tel = telemetry.get_telemetry()
                writer.write_telemetry(tel)
                writer.close({"label": name,
                              "metrics": tel.metrics.snapshot(),
                              "timeline": tel.timeline.export()})
        except Exception as e:
            traceback.print_exc()
            # recorded apart from the measurements: the CSV must carry only
            # real numbers, never a zero-valued ERROR row — and the partial
            # trace (if recording) is sealed by add_failure, not truncated
            rep.add_failure(name, e)
        finally:
            if args.trace:
                telemetry.disable()
                rep.attach_trace(None)
    print(rep.csv())
    if rep.failures:
        print(rep.failure_summary(), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
