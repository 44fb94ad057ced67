"""Transcompilation pipeline — pass sequencing + per-pass correction feedback.

Mirrors the paper's §4.2: after every pass the partial artifact is checked
(compiled / validated) and diagnostics feed back into the generation knobs.
With the LLM replaced by the deterministic planner, the feedback loop's
"revise and fix" step becomes a knob adjustment + rebuild:

  * validation OOB errors      -> engage Pass 4 (pad=True rebuild)
  * VMEM budget errors         -> halve the tile length and rebuild
  * lowering/trace failures    -> recorded as compilation failures (Comp@1)

``transcompile`` lowers a single Program; ``generate_with_feedback`` runs
the outer rebuild loop given a builder callback (the planner or an expert
example).
"""
from __future__ import annotations

import dataclasses
import time
import types
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..dsl import ast as A
from ..dsl.interp import interpret
from ..dsl.validate import validate, DSLValidationError
from ..codegen.emit import emit_module
from .pass2_init import run_pass2
from .pass4_align import needs_refinement


# Lowering-work counters (observability for the artifact cache, DESIGN.md
# §8): ``transcompile`` counts full pass-pipeline runs, ``feedback_builds``
# counts builder invocations inside the correction loop.  A cache hit must
# leave both untouched — tests snapshot-and-diff exactly that.
PIPELINE_COUNTERS: Dict[str, int] = {"transcompile": 0, "feedback_builds": 0}


class TranscompileError(Exception):
    def __init__(self, stage: str, message: str, source: Optional[str] = None):
        self.stage = stage
        self.source = source
        super().__init__(f"[{stage}] {message}")


@dataclass
class Artifact:
    """A generated kernel: the source module + a builder for jitted fns."""
    program: A.Program
    source: str
    module: types.ModuleType
    backend: str
    pass_log: List[str] = field(default_factory=list)
    # knobs the successful build actually used (after feedback adjustments);
    # recorded so the artifact cache can rebuild the program without
    # re-running the correction loop (DESIGN.md §8)
    final_knobs: Optional["Knobs"] = None

    def make(self, shapes: Dict[str, Tuple[int, ...]], interpret: Optional[bool] = None):
        return self.module.make(shapes, interpret=interpret)

    @property
    def entry(self) -> Callable:
        return getattr(self.module, self.program.name)


def _exec_source(source: str, name: str) -> types.ModuleType:
    mod = types.ModuleType(f"repro_generated_{name}")
    mod.__dict__["__name__"] = f"repro_generated_{name}"
    try:
        code = compile(source, f"<generated:{name}>", "exec")
        exec(code, mod.__dict__)
    except Exception as e:  # noqa: BLE001 — feedback loop consumes this
        raise TranscompileError("emit", f"generated source failed to exec: "
                                        f"{type(e).__name__}: {e}", source)
    return mod


def transcompile(prog: A.Program, force_backend: Optional[str] = None,
                 check_shapes: Optional[Dict[str, Tuple[int, ...]]] = None,
                 verify_against_interp: bool = True,
                 rtol: float = 2e-5, atol: float = 1e-5) -> Artifact:
    """Lower one DSL program through passes 1-4 and compile-check it."""
    PIPELINE_COUNTERS["transcompile"] += 1
    log: List[str] = []

    # Pass 0: DSL validation (stage discipline, OOB, budget, alignment)
    rep = validate(prog)
    for d in rep.warnings:
        log.append(f"pass0/validate: {d}")
    if rep.errors:
        raise DSLValidationError(rep.errors)
    log.append(f"pass0/validate: ok ({len(rep.warnings)} warnings)")

    # Pass 2: buffer/queue initialization -> backend selection
    init = run_pass2(prog, force_backend)
    log.append(
        f"pass2/init: backend={init.backend}; "
        f"TQue(in)={sorted(init.bufcls.tque_in)} "
        f"TQue(out)={sorted(init.bufcls.tque_out)} "
        f"TBuf={sorted(init.bufcls.tbuf)}")
    if prog.meta.get("gm_layout"):
        log.append(f"pass4/align: GM layout padded for "
                   f"{sorted(prog.meta['gm_layout'])}")

    # Passes 1+3 (+4 wrapper): emission
    source = emit_module(prog, init, log)
    module = _exec_source(source, prog.name)

    # Compile check: trace + (optionally) numerically verify vs DSL interp.
    # Only runs when check shapes are explicitly provided — interpret-mode
    # execution at benchmark shapes would take minutes on CPU.  make()
    # compiles for the chip on a TPU backend and interprets elsewhere.
    shapes = check_shapes
    if shapes:
        try:
            fn = module.make(shapes)
        except Exception as e:  # noqa: BLE001
            raise TranscompileError(
                "compile", f"make() failed: {type(e).__name__}: {e}", source)
        ins = [tp for tp in prog.kernel.tensors
               if tp.role in (A.Role.IN, A.Role.INOUT)]
        # quantized storage (meta['quant'], DESIGN.md §17): the module
        # entry keeps the f32-in/f32-out contract and quantizes narrow-GM
        # tensors itself; the interpreter instead receives the identical
        # integer codes (the numpy quantizer below is bitwise the entry's
        # jnp one) and its narrow outputs dequantize before comparison.
        quant = prog.meta.get("quant") or {}
        qdt = quant.get("dtype")
        qin_t = quant.get("in", {})
        qout_t = quant.get("out", {})

        def _np_quant(a, inv):
            a = np.asarray(a, np.float32)
            if qdt == "int8":
                return np.clip(
                    np.floor(a * np.float32(inv) + np.float32(0.5)),
                    -127.0, 127.0).astype(np.int8)
            import ml_dtypes
            return np.clip(a * np.float32(inv),
                           -448.0, 448.0).astype(ml_dtypes.float8_e4m3fn)

        rng = np.random.RandomState(0)
        arrays = []
        for tp in ins:
            shp = shapes[tp.name]
            if tp.name in qin_t:
                arrays.append(rng.randn(*shp).astype(np.float32))
            elif tp.dtype in (A.DType.i32,):
                arrays.append(rng.randint(0, 4, shp).astype(np.int32))
            elif tp.dtype is A.DType.b8:
                arrays.append(rng.rand(*shp) > 0.5)
            else:
                arrays.append(rng.randn(*shp).astype(tp.dtype.value))
        try:
            res = fn(*arrays)
        except Exception as e:  # noqa: BLE001
            raise TranscompileError(
                "compile", f"kernel execution failed: {type(e).__name__}: {e}",
                source)
        log.append("compile-check: trace+run ok")
        if verify_against_interp:
            outs = [tp for tp in prog.kernel.tensors
                    if tp.role in (A.Role.OUT, A.Role.INOUT)]
            out_shapes = {tp.name: shapes[tp.name] for tp in outs}
            interp_ins = {
                tp.name: (_np_quant(a, qin_t[tp.name]["inv"])
                          if tp.name in qin_t else a)
                for tp, a in zip(ins, arrays)}
            want = interpret(prog, interp_ins, out_shapes)
            vr = max(rtol, float(quant.get("rtol", 0.0)))
            va = max(atol, float(quant.get("atol", 0.0)))
            got = res if isinstance(res, (tuple, list)) else (res,)
            for tp, g in zip(outs, got):
                wv = want[tp.name].astype(np.float64)
                if tp.name in qout_t:
                    wv = wv * float(qout_t[tp.name]["scale"])
                gv = np.asarray(g, dtype=np.float64)
                if not np.allclose(gv, wv, rtol=vr, atol=va):
                    err = float(np.max(np.abs(gv - wv)))
                    raise TranscompileError(
                        "verify",
                        f"lowered kernel diverges from DSL interpreter on "
                        f"'{tp.name}' (max abs err {err:.3g})", source)
            log.append("verify: lowered == DSL interpreter (oracle) ok")

    return Artifact(program=prog, source=source, module=module,
                    backend=init.backend, pass_log=log)


# --------------------------------------------------------------------------
# Outer feedback loop (planner-level; the paper's per-pass LLM correction)
# --------------------------------------------------------------------------

@dataclass
class Knobs:
    """Generation knobs adjusted by feedback."""
    pad: bool = False
    max_tile: int = 4096
    backend: Optional[str] = None          # force a backend
    extra: Dict[str, Any] = field(default_factory=dict)


def generate_with_feedback(
        builder: Callable[[Knobs], A.Program],
        knobs: Optional[Knobs] = None,
        max_attempts: int = 4,
        **transcompile_kwargs) -> Artifact:
    """Run builder -> validate -> lower with rule-based correction feedback.

    ``builder(knobs)`` constructs the DSL program (planner / expert example).
    """
    knobs = knobs or Knobs()
    history: List[str] = []
    last_exc: Optional[Exception] = None
    for attempt in range(max_attempts):
        PIPELINE_COUNTERS["feedback_builds"] += 1
        try:
            prog = builder(knobs)
        except NotImplementedError:
            raise       # pattern refusal — planner picks another example
        except Exception as e:  # noqa: BLE001
            raise TranscompileError("build", f"builder failed: {e}") from e
        try:
            art = transcompile(prog, force_backend=knobs.backend,
                               **transcompile_kwargs)
            art.pass_log[:0] = history
            art.final_knobs = knobs
            return art
        except DSLValidationError as e:
            last_exc = e
            if any(d.code == "oob" for d in e.diags) and not knobs.pad:
                history.append(
                    f"feedback#{attempt}: OOB diagnostics -> engage pass 4 "
                    f"(padded GM layout)")
                knobs = dataclasses.replace(knobs, pad=True)
                continue
            if any(d.code == "budget" for d in e.diags) and knobs.max_tile > 128:
                history.append(
                    f"feedback#{attempt}: VMEM budget exceeded -> "
                    f"tile {knobs.max_tile} -> {knobs.max_tile // 2}")
                knobs = dataclasses.replace(knobs, max_tile=knobs.max_tile // 2)
                continue
            raise
        except TranscompileError as e:
            last_exc = e
            if e.stage == "verify" and not knobs.pad:
                history.append(
                    f"feedback#{attempt}: numeric divergence -> retry with "
                    f"padded layout")
                knobs = dataclasses.replace(knobs, pad=True)
                continue
            raise
    raise TranscompileError(
        "feedback", f"exhausted {max_attempts} attempts; last: {last_exc}")
