"""Autotuning + persistent artifact cache (DESIGN.md §8).

This package closes the gap the paper leaves open: AscendCraft's feedback
loop (§4.2) repairs kernels until they compile and verify, but never
searches for the *fastest* variant, and re-runs the full transcompile
pipeline for every request.  Here:

* :mod:`.space` — the search space: Knobs axes (tile length, pad policy,
  backend) plus registered program variants (alternative expert builders
  for the same op, e.g. pool2d row reuse).
* :mod:`.tuner` — deterministic budgeted hill climb over that space,
  ranked by the roofline cost model and gated on check-shape correctness.
* :mod:`.cache` — content-addressed on-disk store of emitted kernel
  sources keyed by (task fingerprint, knobs, codegen version); a hit
  skips the whole lowering pipeline.

Entry points: ``planner.generate(task, tune=True, cache=...)`` for the
integrated path, or :func:`tune` / :class:`ArtifactCache` directly.
"""
from .cache import ArtifactCache, CacheEntry, task_fingerprint
from .space import (BACKEND_CHOICES, Candidate, TILE_LADDER,
                    VARIANT_REGISTRY, axis_domains, neighbors,
                    register_axis, register_storage_dtypes,
                    register_variant, reset_registry, storage_dtypes_for,
                    variants_for)
from .tuner import Trial, TuneResult, tune

__all__ = [
    "ArtifactCache", "CacheEntry", "task_fingerprint",
    "BACKEND_CHOICES", "Candidate", "TILE_LADDER", "VARIANT_REGISTRY",
    "axis_domains", "neighbors", "register_axis",
    "register_storage_dtypes", "register_variant", "reset_registry",
    "storage_dtypes_for", "variants_for",
    "Trial", "TuneResult", "tune",
]
