"""Planner — the deterministic generation front-end (the paper's LLM role).

Given a :class:`KernelTask`, the planner

  1. selects the category-specific expert example (paper §4.1),
  2. specializes it to the task's op + shapes (tiling, core partitioning,
     pad policy — the decisions the paper's examples teach the LLM),
  3. runs the multi-pass transcompiler with the per-pass correction
     feedback loop (paper §4.2), and
  4. verifies the artifact: Comp@1 (traces + runs) and Pass@1 (allclose vs
     the task reference AND vs the DSL interpreter oracle at check shapes).

The planner is intentionally pluggable: an LLM front-end can replace
``PLANNER_REGISTRY`` lookup + recipe specialization without touching the
transcompiler (see DESIGN.md §2).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .dsl import ast as A
from .dsl.interp import interpret as dsl_interpret
from .lowering.pipeline import (Artifact, Knobs, TranscompileError,
                                generate_with_feedback)
from .task import KernelTask
from .examples import elementwise as EW
from .examples import normalization as NORM
from .examples import loss as LOSS
from .examples import scan as SCAN
from .examples import reduction as RED
from .examples import pooling as POOL


# --------------------------------------------------------------------------
# op -> (builder factory).  Builder signature: fn(task, shapes, knobs)->Program
# --------------------------------------------------------------------------

def _ew(recipe):
    return lambda task, shapes, knobs: EW.build_elementwise(
        task, shapes, knobs, recipe)


def _rowmap(recipe):
    return lambda task, shapes, knobs: NORM.build_rowwise_map(
        task, shapes, knobs, recipe)


def _rowstat(recipe):
    return lambda task, shapes, knobs: NORM.build_rowwise_stat(
        task, shapes, knobs, recipe)


def _loss(recipe):
    return lambda task, shapes, knobs: LOSS.build_loss_partials(
        task, shapes, knobs, recipe)


PLANNER_REGISTRY: Dict[str, Callable] = {}

# activations / pointwise math (category examples: elementwise)
for _op in EW._SIMPLE_UNARY:
    PLANNER_REGISTRY[_op] = _ew(EW.unary_recipe(_op))
PLANNER_REGISTRY["leaky_relu"] = _ew(EW.leaky_relu_recipe)
PLANNER_REGISTRY["relu6"] = _ew(EW.relu6_recipe)
PLANNER_REGISTRY["hardtanh"] = _ew(EW.hardtanh_recipe)

# optimizers
PLANNER_REGISTRY["sgd"] = _ew(EW.sgd_recipe)
PLANNER_REGISTRY["sgd_momentum"] = _ew(EW.sgd_momentum_recipe)
PLANNER_REGISTRY["adam"] = _ew(EW.adam_recipe)
PLANNER_REGISTRY["adamw"] = _ew(EW.adamw_recipe)
PLANNER_REGISTRY["adagrad"] = _ew(EW.adagrad_recipe)
PLANNER_REGISTRY["rmsprop"] = _ew(EW.rmsprop_recipe)

# normalization (resident rowwise; streaming picked on VMEM overflow)
PLANNER_REGISTRY["softmax"] = _rowmap(NORM.softmax_recipe)
PLANNER_REGISTRY["log_softmax"] = _rowmap(NORM.log_softmax_recipe)
PLANNER_REGISTRY["rmsnorm"] = _rowmap(NORM.rmsnorm_recipe)
PLANNER_REGISTRY["layernorm"] = _rowmap(NORM.layernorm_recipe)
PLANNER_REGISTRY["l2norm"] = _rowmap(NORM.l2norm_recipe)
PLANNER_REGISTRY["l1norm"] = _rowmap(NORM.l1norm_recipe)
PLANNER_REGISTRY["minmax_norm"] = _rowmap(NORM.minmax_norm_recipe)
PLANNER_REGISTRY["instance_norm"] = _rowmap(NORM.instance_norm_recipe)
PLANNER_REGISTRY["softmax_streaming"] = \
    lambda t, s, k: NORM.build_softmax_streaming(t, s, k)
PLANNER_REGISTRY["log_softmax_streaming"] = \
    lambda t, s, k: NORM.build_log_softmax_streaming(t, s, k)
PLANNER_REGISTRY["add_rmsnorm"] = \
    lambda t, s, k: NORM.build_add_rmsnorm(t, s, k)
PLANNER_REGISTRY["rmsnorm_streaming"] = \
    lambda t, s, k: NORM.build_rmsnorm_streaming(t, s, k)

# reduce
PLANNER_REGISTRY["reduce_sum"] = _rowstat(NORM.reduce_sum_recipe)
PLANNER_REGISTRY["reduce_max"] = _rowstat(NORM.reduce_max_recipe)
PLANNER_REGISTRY["reduce_min"] = _rowstat(NORM.reduce_min_recipe)
PLANNER_REGISTRY["reduce_mean"] = _rowstat(NORM.reduce_mean_recipe)
PLANNER_REGISTRY["reduce_prod"] = _rowstat(NORM.reduce_prod_recipe)
PLANNER_REGISTRY["mid_reduce_sum"] = \
    lambda t, s, k: RED.build_mid_reduce(t, s, k, "reduce_sum")
PLANNER_REGISTRY["mid_reduce_mean"] = \
    lambda t, s, k: RED.build_mid_reduce(t, s, k, "reduce_sum", mean=True)

# losses
PLANNER_REGISTRY["mse"] = _loss(LOSS.mse_recipe)
PLANNER_REGISTRY["l1_loss"] = _loss(LOSS.l1_recipe)
PLANNER_REGISTRY["smooth_l1"] = _loss(LOSS.smooth_l1_recipe)
PLANNER_REGISTRY["kl_div"] = _loss(LOSS.kl_div_recipe)
PLANNER_REGISTRY["bce"] = _loss(LOSS.bce_recipe)
PLANNER_REGISTRY["hinge"] = _loss(LOSS.hinge_recipe)
PLANNER_REGISTRY["cosine_sim_loss"] = _rowstat(NORM.cosine_sim_recipe)

# math scans
PLANNER_REGISTRY["cumsum"] = \
    lambda t, s, k: SCAN.build_scan_row(t, s, k, masked=False)
PLANNER_REGISTRY["masked_cumsum"] = \
    lambda t, s, k: SCAN.build_scan_row(t, s, k, masked=True)

# mHC (RQ3)
from .examples import mhc as MHC  # noqa: E402
PLANNER_REGISTRY["mhc_post"] = \
    lambda t, s, k: MHC.build_mhc_post(t, s, k)
PLANNER_REGISTRY["mhc_post_grad"] = \
    lambda t, s, k: MHC.build_mhc_post_grad(t, s, k)
# §Perf row-blocked mhc_post (same bytes, 3 DMA bursts per Rb rows instead
# of 6 per row) — a register_variant entry the tuner discovers via the
# transfer-count tie-break, no longer hand-wired in benchmarks/rq3_mhc.py
PLANNER_REGISTRY["mhc_post_blocked"] = \
    lambda t, s, k: MHC.build_mhc_post_blocked(t, s, k)

# fused operator chains (DESIGN.md §9–§11): every chain the dataflow
# proposer derives gets the UNFUSED sequential program as its registry
# default plus a `<op>_streaming` capacity-refusal fallback; the fused
# form is a tuner-discoverable variant (see tuning/space.py).  Chains are
# no longer hand-declared at any level: fusion/extract.py traces the
# model workload functions (models/workloads.py) with jax.make_jaxpr and
# the proposer segments the normalized graphs — mask_softmax (the
# attention reference's masked score normalization) enters this registry
# purely through extraction.  add_rmsnorm keeps its hand-written expert
# builder as the default — the auto-derived chain rides the variant axis
# to prove parity.
from .fusion import chain as FUSION  # noqa: E402
FUSION.register_planner_chains(PLANNER_REGISTRY)

# pooling
PLANNER_REGISTRY["avg_pool1d"] = \
    lambda t, s, k: POOL.build_pool1d(t, s, k, "avg")
PLANNER_REGISTRY["max_pool1d"] = \
    lambda t, s, k: POOL.build_pool1d(t, s, k, "max")
PLANNER_REGISTRY["lp_pool1d"] = \
    lambda t, s, k: POOL.build_pool1d(t, s, k, "lp2")
PLANNER_REGISTRY["avg_pool2d"] = \
    lambda t, s, k: POOL.build_pool2d(t, s, k, "avg")
PLANNER_REGISTRY["max_pool2d"] = \
    lambda t, s, k: POOL.build_pool2d(t, s, k, "max")
# §Perf hillclimbed variants (beyond-paper; baseline kept for Table 2)
PLANNER_REGISTRY["avg_pool2d_rowreuse"] = \
    lambda t, s, k: POOL.build_pool2d_rowreuse(t, s, k, "avg")
PLANNER_REGISTRY["max_pool2d_rowreuse"] = \
    lambda t, s, k: POOL.build_pool2d_rowreuse(t, s, k, "max")
PLANNER_REGISTRY["global_avg_pool"] = _rowstat(NORM.global_avg_pool_recipe)


# --------------------------------------------------------------------------
# Generation driver
# --------------------------------------------------------------------------

@dataclass
class GenResult:
    task: KernelTask
    artifact: Optional[Artifact]
    comp_ok: bool
    pass_ok: bool
    error: str = ""
    max_abs_err: float = float("nan")
    oracle_ok: Optional[bool] = None
    cached: bool = False        # artifact served from the on-disk cache
    tune: Optional[Any] = None  # TuneResult when generate(tune=True)
    # the check-shape build that Pass@1 ran (None when it was not run)
    check_artifact: Optional[Artifact] = None


def default_inputs(task: KernelTask, shapes: Dict[str, Tuple[int, ...]],
                   seed: int = 0) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(seed)
    if task.make_inputs is not None:
        return task.make_inputs(rng, shapes)
    out = {}
    for tp in task.input_specs:
        shp = shapes[tp.name]
        if tp.dtype is A.DType.i32:
            out[tp.name] = rng.randint(0, 8, shp).astype(np.int32)
        else:
            out[tp.name] = rng.randn(*shp).astype(np.float32)
    return out


@dataclass
class NumericsCheck:
    """Outcome of running a check-shape artifact against the reference.
    ``exec_ok`` distinguishes 'ran but diverged' (Pass@1 failure) from
    'could not run' (Comp@1 failure) explicitly — callers must not infer
    it from the error text."""
    pass_ok: bool
    max_err: float
    error: str
    exec_ok: bool = True


def check_artifact_numerics(task: KernelTask, art_check: Artifact,
                            rtol: float = 3e-4, atol: float = 2e-5,
                            ) -> NumericsCheck:
    """Run a check-shape artifact (compiled on a TPU, interpreted
    elsewhere) and compare against the task reference.  Shared by the
    planner's Pass@1 verification and the tuner's correctness gate."""
    inputs = default_inputs(task, task.check_shapes)
    arrays = [inputs[tp.name] for tp in task.input_specs]
    try:
        got = art_check.entry(*arrays)
    except Exception as e:  # noqa: BLE001
        return NumericsCheck(False, float("nan"),
                             f"execution failed: {e}", exec_ok=False)

    want = task.ref(*arrays)
    gots = got if isinstance(got, (tuple, list)) else (got,)
    wants = want if isinstance(want, (tuple, list)) else (want,)
    if len(gots) != len(wants):
        return NumericsCheck(False, float("nan"),
                             f"output count mismatch: kernel returned "
                             f"{len(gots)}, reference returned {len(wants)}")
    max_err, ok = 0.0, True
    for g, wv in zip(gots, wants):
        g = np.asarray(g, dtype=np.float64)
        wv = np.asarray(wv, dtype=np.float64)
        if g.shape != wv.shape:
            return NumericsCheck(False, float("nan"),
                                 f"shape mismatch {g.shape} vs {wv.shape}")
        scale = np.maximum(np.abs(wv), 1.0)
        err = float(np.max(np.abs(g - wv) / scale)) if g.size else 0.0
        max_err = max(max_err, err)
        if not np.allclose(g, wv, rtol=rtol, atol=atol):
            ok = False
    return NumericsCheck(ok, max_err,
                         "" if ok else f"max rel err {max_err:.3g}")


def fallback_op_for(op: str) -> str:
    """Registry key of the op's capacity-refusal fallback builder.

    Convention: ``<op>_streaming`` — the long-row form a resident builder
    hands off to when it raises ``NotImplementedError``."""
    return f"{op}_streaming"


def resolve_and_build(task: KernelTask, builder: Callable, variant: str,
                      knobs: Optional[Knobs],
                      shapes: Dict[str, Tuple[int, ...]],
                      **transcompile_kwargs) -> Tuple[Artifact, str]:
    """The ONE resident→fallback resolve-and-build policy (shared by the
    planner's bench path, its check-shape build, and the tuner's
    evaluator, so the three cannot desynchronize).

    Runs ``builder`` through the correction-feedback loop at ``shapes``;
    when it refuses with ``NotImplementedError`` (row too long / VMEM
    overflow) and the candidate is the *default* variant, retries once
    with the op's registered fallback builder (``fallback_op_for``).
    Returns ``(artifact, resolved_op)`` — ``resolved_op`` is the registry
    key of the builder that actually produced the artifact, recorded so
    later check-shape builds verify the same program family."""
    try:
        art = generate_with_feedback(
            lambda kn: builder(task, shapes, kn), knobs,
            **transcompile_kwargs)
        return art, task.op
    except NotImplementedError:
        fb_op = fallback_op_for(task.op)
        if variant != "default" or fb_op not in PLANNER_REGISTRY:
            raise
        fb_builder = PLANNER_REGISTRY[fb_op]
        # carry the dtype-axis specialization across the fallback: a
        # quantized request must not silently degrade to the f32 fallback
        axes = getattr(builder, "axes", None)
        if axes:
            with_axes = getattr(fb_builder, "with_axes", None)
            if with_axes is None:
                raise
            fb_builder = with_axes(axes)
        art = generate_with_feedback(
            lambda kn: fb_builder(task, shapes, kn), knobs,
            **transcompile_kwargs)
        return art, fb_op


def generate(task: KernelTask, knobs: Optional[Knobs] = None,
             verify: bool = True, rtol: float = 3e-4,
             atol: float = 2e-5, *, tune: bool = False,
             tune_budget: int = 12, cache=None) -> GenResult:
    """AscendCraft pipeline for one task: plan -> DSL -> transcompile ->
    verify.  Never raises for generation failures — returns the scoreable
    result (Comp@1 / Pass@1), as the benchmark does.

    Beyond-paper extensions (DESIGN.md §8):

    * ``cache=`` — ``True`` / an ``ArtifactCache`` / a directory path.  The
      emitted source is memoized on (task fingerprint, knobs, codegen
      version); a hit skips the entire lowering pipeline.
    * ``tune=`` — run the budgeted hill-climb autotuner first and generate
      with the best (variant, knobs) it finds; the winning candidate is
      remembered in the cache, so later tuned calls are O(1).
    """
    # fault hook (DESIGN.md §14): an armed raise here models a front-end/
    # builder exception ESCAPING the generator — the failure mode the
    # degradation ladder and warm_kernel_cache's per-task isolation absorb
    from .resilience.faults import fault_point
    fault_point("planner.generate", token=task.name)

    def _emit_result(res: GenResult) -> GenResult:
        # exit transform hook: lets a FaultPlan poison a green result
        # (e.g. NaN-producing artifact) to exercise the runtime sentinel
        return fault_point("planner.generate:result", res, token=task.name)

    if task.op not in PLANNER_REGISTRY:
        return GenResult(task, None, False, False,
                         error=f"no expert example registered for op "
                               f"'{task.op}'")
    from .tuning.cache import ArtifactCache
    cache_obj = ArtifactCache.resolve(cache)

    builder_fn = PLANNER_REGISTRY[task.op]
    variant = "default"
    tune_result = None
    axes: Dict[str, str] = {}
    # pinned dtype axes (task.attrs['axes'], e.g. a serving engine keyed
    # on --kv-dtype): applied ALWAYS — tuned or not — and folded into the
    # cache fingerprint below, so a warmed f32 entry can never serve a
    # quantized request
    pinned_axes = {k: str(v)
                   for k, v in dict(task.attrs.get("axes") or {}).items()
                   if str(v) != "f32"}
    if tune:
        from .tuning.space import Candidate, variants_for
        from .tuning.tuner import tune as run_tune
        best_cand = None
        # a tuned pointer short-circuits the search, but only when the
        # caller didn't constrain knobs — explicit knobs seed the climb
        if cache_obj is not None and knobs is None:
            rec = cache_obj.get_tuned(task)
            if rec is not None:
                try:
                    # from_dict tolerates schema skew both ways: legacy
                    # pre-axis pointers fill the axis defaults, future
                    # extra keys drop (the migration path for the
                    # axis-product refactor)
                    best_cand = Candidate.from_dict(rec["candidate"])
                except (TypeError, ValueError):
                    best_cand = None
        if best_cand is None:
            start = None
            if knobs is not None or pinned_axes:
                base = ({} if knobs is None else
                        {"max_tile": knobs.max_tile, "pad": knobs.pad,
                         "backend": knobs.backend})
                start = Candidate(**base, **pinned_axes)
            tune_result = run_tune(task, budget=tune_budget, cache=cache_obj,
                                   start=start, rtol=rtol, atol=atol)
            best_cand = tune_result.best.candidate
        if best_cand.variant != "default":
            vb = variants_for(task.op).get(best_cand.variant)
            if vb is not None:
                builder_fn = vb
                variant = best_cand.variant
        knobs = best_cand.to_knobs()
        axes = best_cand.dtype_axes()
    axes = {**axes, **pinned_axes}
    if axes:
        with_axes = getattr(builder_fn, "with_axes", None)
        if with_axes is None:
            return GenResult(task, None, False, False,
                             error=f"op '{task.op}' (variant '{variant}') "
                                   f"does not support dtype axes {axes}")
        builder_fn = with_axes(axes)
    # quantized builders verify at their dtype-derived bar, never tighter
    rtol = max(rtol, float(getattr(builder_fn, "verify_rtol", 0.0)))
    atol = max(atol, float(getattr(builder_fn, "verify_atol", 0.0)))

    # ---- artifact cache fast path ---------------------------------------
    req_knobs = knobs or Knobs()
    cache_key = None
    if cache_obj is not None:
        cache_key = cache_obj.key_for(task, req_knobs, variant=variant,
                                      axes=axes)
        entry = cache_obj.get(cache_key)
        if entry is not None and not (
                verify and
                not cache_obj.verdict_covers(entry.meta, rtol, atol)):
            art = cache_obj.materialize(task, entry)
            if art is not None:
                meta = entry.meta
                cached_err = meta.get("max_abs_err")
                # a verdict that came from an execution failure is a
                # Comp@1 failure, same as the uncached path reports; under
                # verify=False no verdict is consulted (the uncached path
                # returns (True, True) there too)
                comp_ok = (meta.get("exec_ok", True) is not False
                           if verify else True)
                return _emit_result(GenResult(
                    task, art, comp_ok,
                    bool(meta["pass_ok"]) if verify else True,
                    error=meta.get("error", "") if verify else "",
                    max_abs_err=(float("nan") if cached_err is None
                                 else float(cached_err)),
                    cached=True, tune=tune_result))

    resolved_op = task.op

    # An entry that exists but lacks a covering verdict still spares the
    # bench-shape lowering: materialize its source and only pay the
    # check-shape verification below (mirrors the tuner's late-gate path).
    art = None
    cached_bench = False
    if cache_obj is not None and entry is not None and verify:
        art = cache_obj.materialize(task, entry)
        if art is not None:
            cached_bench = True
            resolved_op = entry.meta.get("resolved_op", task.op)

    try:
        if art is None:
            art, resolved_op = resolve_and_build(
                task, builder_fn, variant, knobs, task.shapes,
                check_shapes=None, verify_against_interp=False)
    except Exception as e:  # noqa: BLE001
        return GenResult(task, None, False, False, error=str(e))

    if not verify:
        if cache_obj is not None:
            cache_obj.put(cache_key, art, task=task, variant=variant,
                          resolved_op=resolved_op, pass_ok=None, axes=axes)
        return _emit_result(GenResult(task, art, True, True,
                                      tune=tune_result))

    # ---- Comp@1 + Pass@1 at check shapes --------------------------------
    # Generated kernels are shape-specialized (as in the paper); numeric
    # verification uses a check-shape build of the same pipeline, while the
    # bench-shape artifact above feeds the performance model / Comp@1.
    # The check build must verify the SAME program family as the bench
    # artifact: if the bench path resolved to the streaming builder (via
    # refusal now, or recorded in the cached entry), check with it directly
    # — the resident builder may not refuse at the smaller check shapes,
    # and verifying a different program would persist a wrong verdict.
    check_builder_fn = builder_fn
    if variant == "default" and resolved_op != task.op:
        check_builder_fn = PLANNER_REGISTRY.get(resolved_op, builder_fn)
        if axes and check_builder_fn is not builder_fn:
            # the registry fallback is unspecialized — re-apply the dtype
            # axes (or keep the already-specialized original builder)
            wa = getattr(check_builder_fn, "with_axes", None)
            check_builder_fn = (wa(axes) if wa is not None else builder_fn)
    elif art is not None:
        # family hook (fusion chains): a pattern-auto builder resolves by
        # shape, so the small check shapes could verify a resident program
        # while the bench artifact streams — ask the builder for a
        # same-pattern check builder instead
        hook = getattr(builder_fn, "check_builder_for", None)
        if hook is not None:
            check_builder_fn = hook(art.program) or builder_fn

    try:
        art_check, _ = resolve_and_build(
            task, check_builder_fn, variant, knobs, task.check_shapes,
            check_shapes=None, verify_against_interp=False)
    except Exception as e:  # noqa: BLE001
        return GenResult(task, art, False, False,
                         error=f"check-shape build failed: {e}",
                         cached=cached_bench, tune=tune_result)
    chk = check_artifact_numerics(task, art_check, rtol, atol)
    if not chk.exec_ok:
        # persist the execution failure so the cache serves it as a
        # Comp@1 failure instead of re-paying this build + run each call
        if cache_obj is not None:
            if cached_bench:
                cache_obj.update_meta(cache_key, pass_ok=False,
                                      exec_ok=False, error=chk.error,
                                      verify_rtol=rtol, verify_atol=atol)
            else:
                cache_obj.put(cache_key, art, task=task, variant=variant,
                              resolved_op=resolved_op, pass_ok=False,
                              exec_ok=False, error=chk.error,
                              verify_rtol=rtol, verify_atol=atol, axes=axes)
        return GenResult(task, art, False, False, error=chk.error,
                         cached=cached_bench, tune=tune_result,
                         check_artifact=art_check)
    if cache_obj is not None:
        if cached_bench:
            # source already on disk: just persist the fresh verdict
            # (including exec_ok, which may clear a stale failure)
            cache_obj.update_meta(cache_key, pass_ok=chk.pass_ok,
                                  max_abs_err=chk.max_err, error=chk.error,
                                  exec_ok=chk.exec_ok,
                                  verify_rtol=rtol, verify_atol=atol)
        else:
            cache_obj.put(cache_key, art, task=task, variant=variant,
                          resolved_op=resolved_op, pass_ok=chk.pass_ok,
                          max_abs_err=chk.max_err, error=chk.error,
                          verify_rtol=rtol, verify_atol=atol, axes=axes)

    # DSL-interpreter oracle equivalence is property-tested in tests/core
    # (lowered pallas == numpy interpreter on randomly generated programs).
    return _emit_result(GenResult(
        task, art, True, chk.pass_ok, max_abs_err=chk.max_err,
        error=chk.error, oracle_ok=None, cached=cached_bench,
        tune=tune_result, check_artifact=art_check))
