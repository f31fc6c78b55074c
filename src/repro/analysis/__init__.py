"""Whole-program static analysis + runtime sanitizer (DESIGN.md §12, §15).

Layers:

- :mod:`repro.analysis.lint` — AST lint engine with JAX-aware rules
  (host-sync-in-jit, retrace-hazard, np-jnp-mixing, frozen-mutation,
  unordered-iteration, exactness-contract, topology-config,
  registry-counter-mutation, and the numerics and determinism rules
  int32-overflow / unseeded-rng / wall-clock-leak / unbounded-signature /
  interproc-unordered-iteration);
- :mod:`repro.analysis.callgraph` — whole-program layer: import
  resolution, cross-module jit traced-set closure, interprocedural rules
  (:func:`lint_program` is the CLI/CI entry point);
- :mod:`repro.analysis.numerics` — abstract integer-width/overflow pass
  against :data:`repro.analysis.contracts.SCALE_TARGET`;
- :mod:`repro.analysis.determinism` — RNG, wall-clock, and
  jit-signature-space determinism rules;
- :mod:`repro.analysis.contracts` — the scheme × engine exactness table,
  static mirrors of the runtime topology/config build errors, and the
  determinism/numerics targets;
- :mod:`repro.analysis.sanitize` — the dynamic twin: same-seed double-run
  under strict numerics, reports diffed bit-for-bit;
- :mod:`repro.analysis.audit` — runtime trace/transfer auditor for the
  fused engine's jit boundaries.

CLI: ``python -m repro.analysis [paths...]`` (see :mod:`.cli`), gated in
CI against the checked-in ``analysis_baseline.json``.

This package is import-light: pulling in the contracts table or the lint
engine must not drag jax or numpy in (the CI lint job stays fast and
dependency-free), so jax/numpy-touching imports live inside functions.
"""

from .callgraph import lint_program
from .contracts import (BANDED_SCHEMES, DRIFT_SCHEMES, EXACT_SCHEMES,
                        EXACTNESS, SCALE_TARGET, SCHEMES, exactness)
from .findings import Baseline, Finding, apply_baseline
from .lint import RULES, lint_file, lint_paths

__all__ = [
    "SCHEMES", "EXACTNESS", "EXACT_SCHEMES", "BANDED_SCHEMES",
    "DRIFT_SCHEMES", "exactness", "SCALE_TARGET",
    "Finding", "Baseline", "apply_baseline",
    "RULES", "lint_file", "lint_paths", "lint_program",
]
