"""Hill-climb autotuner over the knob/variant space (DESIGN.md §8).

Role in the paper's pipeline: sits *after* the feedback loop (§4.2).  The
feedback loop turns a candidate into a compiling, verified kernel; the
tuner decides *which* candidate to build, ranking points of
:mod:`repro.core.tuning.space` by the deterministic roofline cost model
(``repro.bench.model.fast_ratio``) and gating every candidate on
correctness: the check-shape build must run (compiled on a TPU, under the
Pallas interpreter elsewhere) and match the task reference within the
planner's tolerances.

Search: greedy hill climb with a hard evaluation budget.  Start from the
default candidate, evaluate every single-axis neighbor (deterministic
order — no RNG anywhere, so a fixed budget always yields the same trial
sequence and the same winner), move to the best strict improvement,
repeat until a local optimum or budget exhaustion.  Every bench-shape
artifact the tuner builds is pushed through the persistent artifact cache,
so re-tunes and later ``generate()`` calls hit cached sources.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..lowering.pipeline import Knobs
from .cache import ArtifactCache
from .space import Candidate, neighbors, variants_for

_EPS = 1e-9
# near-tie band for the DMA-burst tie-break: candidates whose modeled
# ratio is within 0.1% count as "same bytes" (e.g. the mHC row-blocked
# kernel re-reads the tiny sinkhorn inputs once per block — ~1e-6 more
# bytes — while cutting transfers 38x)
_TIE_EPS = 1e-3


@dataclass
class Trial:
    candidate: Candidate
    ratio: float                 # fast_ratio at bench shapes (0 if failed)
    ok: bool                     # built AND passed the correctness gate
    error: str = ""
    from_cache: bool = False
    transfers: int = 0           # modeled DMA bursts (tie-break metric)


@dataclass
class TuneResult:
    task_name: str
    op: str
    default: Trial               # the un-tuned baseline candidate
    best: Trial                  # highest correct ratio found
    trials: List[Trial] = field(default_factory=list)
    evaluations: int = 0
    budget: int = 0

    @property
    def improvement(self) -> float:
        """best/default fast_ratio (1.0 = tuning found nothing better)."""
        if self.default.ratio <= 0:
            return float("inf") if self.best.ratio > 0 else 1.0
        return self.best.ratio / self.default.ratio

    def summary(self) -> str:
        return (f"{self.task_name}: default {self.default.ratio:.2f}x -> "
                f"tuned {self.best.ratio:.2f}x "
                f"({self.best.candidate.describe()}) "
                f"in {self.evaluations}/{self.budget} evals")


# --------------------------------------------------------------------------
# Candidate evaluation
# --------------------------------------------------------------------------

def _evaluate(task, cand: Candidate, cache: Optional[ArtifactCache],
              rtol: float, atol: float, gate: bool) -> Trial:
    from ..planner import check_artifact_numerics     # lazy (import cycle)
    from ...bench.model import (analyze_program, eager_traffic,
                                _padded_shapes_for)

    builder = variants_for(task.op).get(cand.variant)
    if builder is None:
        return Trial(cand, 0.0, False, f"unknown variant '{cand.variant}'")
    axes = cand.dtype_axes()
    if axes:
        # non-default dtype-axis assignment: specialize the builder (a
        # builder without the hook has a single-point dtype domain — the
        # candidate cannot build)
        with_axes = getattr(builder, "with_axes", None)
        if with_axes is None:
            return Trial(cand, 0.0, False,
                         f"variant '{cand.variant}' does not support "
                         f"axes {axes}")
        builder = with_axes(axes)
    # quantized builders carry their dtype-derived verification bar; the
    # gate never tightens below the caller's request
    rtol = max(rtol, float(getattr(builder, "verify_rtol", 0.0)))
    atol = max(atol, float(getattr(builder, "verify_atol", 0.0)))
    knobs = cand.to_knobs()

    # Bench-shape artifact (feeds the cost model) — through the cache.
    art, from_cache, cached_verdict_ok = None, False, False
    resolved_op = task.op
    key = (cache.key_for(task, knobs, variant=cand.variant, axes=axes)
           if cache is not None else None)
    if cache is not None:
        entry = cache.get(key)
        if entry is not None:
            resolved_op = entry.meta.get("resolved_op", task.op)
            # a covering FAILED verdict makes the candidate a cheap skip —
            # no point rebuilding a kernel known not to verify
            if (gate and entry.meta.get("pass_ok") is False and
                    cache.verdict_covers(entry.meta, rtol, atol)):
                return Trial(cand, 0.0, False,
                             entry.meta.get("error")
                             or "correctness gate failed (cached verdict)",
                             from_cache=True)
            art = cache.materialize(task, entry)
            from_cache = art is not None
            if from_cache:
                cached_verdict_ok = (
                    entry.meta.get("pass_ok") is True and
                    cache.verdict_covers(entry.meta, rtol, atol))
    if art is None:
        # same resident->fallback policy as the planner's bench path
        # (shared helper — the two must not desynchronize)
        from ..planner import resolve_and_build
        try:
            art, resolved_op = resolve_and_build(
                task, builder, cand.variant, dataclasses.replace(knobs),
                task.shapes, check_shapes=None, verify_against_interp=False)
        except Exception as e:  # noqa: BLE001 — a failed point scores 0
            return Trial(cand, 0.0, False, f"build failed: {e}")

    try:
        # one cost-model pass per trial: ratio and the tie-break transfer
        # count come from the same Traffic analysis
        gen = analyze_program(
            art.program, _padded_shapes_for(art.program, task.shapes))
        ratio = float(eager_traffic(task, task.shapes).time_s()
                      / max(gen.time_s(), 1e-30))
        transfers = gen.transfers
    except Exception as e:  # noqa: BLE001
        return Trial(cand, 0.0, False, f"cost model failed: {e}")

    # Correctness gate: check-shape build must run and must
    # match the task reference (same bar the planner's Pass@1 applies).
    # A cached entry that already carries pass_ok=True was gated at the
    # same bar when stored — don't pay the check-shape build again.
    ok, err_msg, gate_err = True, "", None
    if gate and cached_verdict_ok:
        gate = False
    gate_ran = gate and task.ref is not None
    gate_exec_ok = True
    if gate_ran:
        # gate the same program family the artifact was built from: a
        # cached entry may record a streaming resolved_op even though the
        # default builder would not refuse at the smaller check shapes
        gate_builder = builder
        if cand.variant == "default" and resolved_op != task.op:
            from ..planner import PLANNER_REGISTRY
            gate_builder = PLANNER_REGISTRY.get(resolved_op, builder)
            if axes and gate_builder is not builder:
                # the fallback registry builder is unspecialized; re-apply
                # the candidate's axes (or keep the specialized original)
                wa = getattr(gate_builder, "with_axes", None)
                gate_builder = wa(axes) if wa is not None else builder
        else:
            # same-family hook for pattern-auto builders (fusion chains):
            # force the check build to the bench artifact's resident /
            # streaming pattern
            hook = getattr(builder, "check_builder_for", None)
            if hook is not None:
                gate_builder = hook(art.program) or builder
        from ..planner import resolve_and_build
        try:
            art_check, _ = resolve_and_build(
                task, gate_builder, cand.variant,
                dataclasses.replace(knobs), task.check_shapes,
                check_shapes=None, verify_against_interp=False)
            chk = check_artifact_numerics(task, art_check, rtol, atol)
            ok, err_msg, gate_err = chk.pass_ok, chk.error, chk.max_err
            gate_exec_ok = chk.exec_ok
        except Exception as e:  # noqa: BLE001
            ok, err_msg = False, f"check-shape build failed: {e}"
            gate_exec_ok = False
        if from_cache and cache is not None:
            # persist the late verdict so future tunes/generates against
            # this cache never re-pay the gate for the same entry
            cache.update_meta(key, pass_ok=ok, error=err_msg,
                              max_abs_err=gate_err, exec_ok=gate_exec_ok,
                              verify_rtol=rtol, verify_atol=atol)
    if not ok:
        if cache is not None and not from_cache:
            # persist the failing verdict too: the next tune() skips this
            # candidate without rebuilding anything
            cache.put(key, art, task=task, variant=cand.variant,
                      resolved_op=resolved_op, pass_ok=False,
                      max_abs_err=gate_err, error=err_msg,
                      exec_ok=gate_exec_ok,
                      verify_rtol=rtol, verify_atol=atol, axes=axes)
        return Trial(cand, 0.0, False, err_msg or "correctness gate failed",
                     from_cache=from_cache)

    if cache is not None and not from_cache:
        cache.put(key, art, task=task, variant=cand.variant,
                  resolved_op=resolved_op,
                  pass_ok=(True if gate_ran else None),
                  max_abs_err=gate_err, ratio=ratio,
                  verify_rtol=rtol if gate_ran else None,
                  verify_atol=atol if gate_ran else None, axes=axes)
    return Trial(cand, ratio, True, from_cache=from_cache,
                 transfers=transfers)


# --------------------------------------------------------------------------
# The hill climb
# --------------------------------------------------------------------------

def tune(task, budget: int = 12, cache=None,
         start: Optional[Candidate] = None,
         rtol: float = 3e-4, atol: float = 2e-5,
         gate: bool = True) -> TuneResult:
    """Search the knob/variant space for the fastest correct build of
    ``task``.  ``budget`` caps the number of candidate evaluations, with a
    floor of 1 — the baseline candidate is always evaluated (cache hits
    count too; the budget bounds search effort, and cached evaluations are
    what make re-tuning cheap).  Deterministic: same task + budget => same
    trials, same winner."""
    budget = max(1, int(budget))
    cache = ArtifactCache.resolve(cache)
    seen: Dict[Candidate, Trial] = {}
    result = TuneResult(task_name=task.name, op=task.op,
                        default=None, best=None, budget=budget)  # type: ignore[arg-type]

    def ev(cand: Candidate) -> Trial:
        if cand in seen:
            return seen[cand]
        t = _evaluate(task, cand, cache, rtol, atol, gate)
        seen[cand] = t
        result.trials.append(t)
        result.evaluations += 1
        return t

    current = start or Candidate()
    cur = ev(current)
    result.default = cur
    best = cur

    def improves(t: Trial, over: Trial) -> bool:
        """Strictly better: a clear modeled-ratio win, or — the bytes
        model cannot see DMA-burst granularity — a near-tie (within
        ``_TIE_EPS``) with strictly fewer transfers (e.g. the mHC
        row-blocked variant moves the same bytes in 3 bursts per block
        instead of 6 per row).  Inside the near-tie band a sub-0.1% ratio
        edge only wins when it does not regress the transfer count."""
        base = max(over.ratio, 0.0)
        if t.ratio > base * (1 + _TIE_EPS):
            return True
        if t.ratio < over.ratio * (1 - _TIE_EPS):
            return False
        if 0 < t.transfers < over.transfers:
            return True
        return t.ratio > base * (1 + _EPS) and t.transfers <= over.transfers

    # dtype axes are a per-task opt-in (task.attrs['tuner_axes']): a
    # numerics-changing axis never silently enters an existing op's
    # search, and f32 tuned pointers stay byte-stable
    open_axes = tuple(task.attrs.get("tuner_axes", ()) or ())
    while result.evaluations < budget:
        step_best: Optional[Trial] = None
        for nb in neighbors(current, task.op, open_axes):
            if result.evaluations >= budget:
                break
            if nb in seen:
                continue
            t = ev(nb)
            if t.ok and (step_best is None or improves(t, step_best)):
                step_best = t
        if step_best is None or not improves(step_best, best):
            break                                   # local optimum
        best = step_best
        current = step_best.candidate

    result.best = best if (best.ok or not result.trials) else result.default
    if cache is not None and result.best.ok:
        # never clobber a better previously-found pointer with the result
        # of a narrower (constrained / low-budget) search
        prev = cache.get_tuned(task)
        if prev is None or result.best.ratio > float(prev.get("ratio", 0.0)):
            cache.put_tuned(task, result.best.candidate, result.best.ratio)
    return result
