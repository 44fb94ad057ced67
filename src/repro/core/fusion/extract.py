"""Jaxpr-level graph extraction for the fusion proposer (DESIGN.md §11).

Until this module landed, the proposer (``propose.py``) consumed
*hand-declared* :class:`OpGraph` workloads — a human read the model code
and transcribed its dataflow.  ``extract.py`` closes that gap: it traces
real model functions (``models/workloads.py`` — residual blocks, norm
epilogues, the attention score pipeline) with :func:`jax.make_jaxpr` and
normalizes the jaxpr into the *same* OpGraph IR, so chains are discovered
from the model itself and flow through the unchanged
``propose_chains → ChainSpec → planner/tuner`` pipeline.

Normalization layers (in order):

1. **Flattening** — ``jit`` / ``custom_jvp_call`` / ``custom_vjp_call``
   wrappers are inlined recursively (``jax.nn.silu`` arrives as a jit
   named ``silu``; ``scan``/``while``/``cond`` are *not* inlined — their
   sub-jaxprs stay opaque barriers).
2. **Aliasing** — semantic no-ops vanish: ``convert_element_type``,
   ``copy``, ``stop_gradient``, identity arithmetic (``max(x, -inf)``,
   ``add(x, 0)``, ``mul(x, 1)``), trailing-preserving reshapes, and
   ``broadcast_in_dim`` (classified as *trailing* row-broadcast of a
   vector, *keepdims* expansion of a reduction, or scalar fill).
3. **Composite recognition** — multi-primitive idioms collapse into the
   proposer's op vocabulary: ``softmax`` (reduce_max → sub → exp →
   reduce_sum → div), ``rmsnorm`` (mean-of-squares → rsqrt → scale),
   ``gelu`` (both the tanh and the erf/erfc forms), ``silu``
   (``x·σ(x)``), ``relu`` (``max(x, 0)``), ``swiglu`` (``silu(a)·b``) and
   ``square`` (``integer_pow[2]``).
4. **Masked-fill canonicalization** — ``where(pred, x, -inf)`` feeding a
   softmax is the additive-mask idiom in disguise: the select is rewritten
   to ``add(x, mask)`` with a synthesized external ``mask`` input (sound
   because softmax's neutral element absorbs the fill; the rewrite is
   gated on every consumer being a softmax row input).
5. **Barrier classification** — every remaining primitive (dots, scans,
   control flow, slicing, transposes, scalar-operand arithmetic,
   reductions that did not fold into a composite) becomes a non-fusable
   ``barrier.<prim>`` node, exactly like ``matmul`` in the hand-declared
   graphs: the proposer segments around it and its output re-enters
   downstream chains as a plain input.

Name stability: proposed chains are canonically renamed
(:func:`canonicalize_spec`) and fingerprinted (α-invariant
:func:`~repro.core.fusion.propose.chain_fingerprint`); ``chain.py``
resolves a fingerprint match against the declared golden fixtures to the
fixture's spec verbatim, so registry entries, cache keys and
``kernels/generated/`` artifacts never churn when extraction re-derives a
known chain.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np
from jax.extend.core import Literal

from .propose import OpGraph, OpNode, ProposeError, propose_chains


class ExtractError(ProposeError):
    """The traced function cannot be normalized into an OpGraph."""


# --------------------------------------------------------------------------
# Primitive coverage (DESIGN.md §11 table)
# --------------------------------------------------------------------------

# single jaxpr primitive -> proposer op (tensor-operand forms only)
PRIM_MAP: Dict[str, str] = {
    "add": "add", "sub": "sub", "mul": "mul",
    "tanh": "tanh", "exp": "exp", "abs": "abs", "neg": "neg",
    "sqrt": "sqrt", "logistic": "sigmoid",
}

# call-like primitives whose sub-jaxpr is inlined during flattening
# (``remat2`` is the modern ``jax.checkpoint`` primitive: VJPs of
# checkpointed functions arrive wrapped in it, and refusing to inline it
# made every checkpointed backward graph an opaque barrier)
INLINE_PRIMS = frozenset((
    "jit", "closed_call", "core_call", "named_call", "remat",
    "remat2", "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
))

# semantic no-ops that alias their input
ALIAS_PRIMS = frozenset((
    "convert_element_type", "copy", "stop_gradient", "reduce_precision",
))

_BIG_NEG = -1.0e30          # masked-fill threshold (−inf, −3e38, ...)


def _isclose(a: float, b: float, rel: float = 1e-3) -> bool:
    return abs(a - b) <= rel * max(1.0, abs(b))


# --------------------------------------------------------------------------
# Normalized IR: SSA values + equations
# --------------------------------------------------------------------------

@dataclass(eq=False)
class _Val:
    vid: int
    shape: Tuple[int, ...]
    kind: str                      # 'ext' | 'const' | 'op'
    name: str = ""                 # ext: argument name (or synthesized)
    const: Any = None              # const: python/numpy value
    base: Optional["_Val"] = None  # broadcast alias target
    bkind: str = ""                # '' | 'trail' | 'keep' | 'scalar'


def _base(v: _Val) -> _Val:
    while v.base is not None:
        v = v.base
    return v


def _scalar_const(v: _Val) -> Optional[float]:
    """The scalar value of ``v`` if it resolves to a 0-d (or size-1)
    constant, else None."""
    b = _base(v)
    if b.kind != "const":
        return None
    arr = np.asarray(b.const)
    if arr.size != 1:
        return None
    return float(arr.reshape(()))


@dataclass(eq=False)
class _Eqn:
    prim: str                      # jaxpr primitive OR recognized composite
    ins: List[_Val]
    out: _Val
    params: Dict[str, Any] = field(default_factory=dict)


# --------------------------------------------------------------------------
# Jaxpr -> IR flattening
# --------------------------------------------------------------------------

class _Builder:
    def __init__(self):
        self.eqns: List[_Eqn] = []
        self._next = 0

    def val(self, shape, kind, **kw) -> _Val:
        self._next += 1
        return _Val(self._next, tuple(int(s) for s in shape), kind, **kw)

    def _alias_identity(self, prim, ins) -> Optional[_Val]:
        """Identity arithmetic: max(x, -inf), min(x, inf), add/sub(x, 0),
        mul(x, 1) alias the tensor operand."""
        if len(ins) != 2:
            return None
        for i, j in ((0, 1), (1, 0)):
            c = _scalar_const(ins[i])
            t = ins[j]
            if c is None or _base(t).kind == "const":
                continue
            if prim == "max" and c == float("-inf"):
                return t
            if prim == "min" and c == float("inf"):
                return t
            if prim == "add" and c == 0.0:
                return t
            if prim == "mul" and c == 1.0:
                return t
            if prim == "sub" and c == 0.0 and j == 0:
                return t
        return None

    def emit(self, prim: str, ins: List[_Val], out_shape, params) -> _Val:
        alias = self._alias_identity(prim, ins)
        if alias is not None and tuple(alias.shape) == tuple(out_shape):
            return alias
        if prim == "neg" and len(ins) == 1:
            # fold neg of a scalar constant so downstream mul-by-const
            # normalization (scale / identity aliasing) sees the signed
            # value — VJP graphs negate literal cotangent seeds
            c = _scalar_const(ins[0])
            if c is not None:
                return self.val(out_shape, "const", const=np.asarray(-c))
        out = self.val(out_shape, "op")
        self.eqns.append(_Eqn(prim, list(ins), out, dict(params)))
        return out

    def broadcast(self, src: _Val, out_shape, dims) -> _Val:
        """Classify a broadcast_in_dim: trailing row-broadcast, keepdims
        expansion, scalar fill — or an opaque barrier eqn."""
        out_shape = tuple(int(s) for s in out_shape)
        dims = tuple(int(d) for d in dims)
        in_shape = src.shape
        r_in, r_out = len(in_shape), len(out_shape)
        sizes_kept = all(out_shape[d] == in_shape[i]
                         for i, d in enumerate(dims))
        if r_in == 0 or (_base(src).kind == "const"
                         and np.asarray(_base(src).const).size == 1):
            return self.val(out_shape, "const", const=_base(src).const,
                            base=src if _base(src).kind != "const" else None,
                            bkind="scalar") if _base(src).kind == "const" \
                else self.val(out_shape, "op", base=src, bkind="scalar")
        if sizes_kept and dims == tuple(range(r_out - r_in, r_out)):
            return self.val(out_shape, "op", base=src, bkind="trail")
        if sizes_kept and dims == tuple(range(r_in)):
            if all(s == 1 for s in out_shape[r_in:]):
                return self.val(out_shape, "op", base=src, bkind="keep")
            # leading-axes-kept broadcast along new trailing axes: the
            # transposed-jaxpr form of a keepdims expansion (VJP graphs
            # drop the size-1 axis before re-broadcasting a row stat)
            return self.val(out_shape, "op", base=src, bkind="row")
        return self.emit("broadcast_in_dim", [src], out_shape,
                         {"dims": dims})

    # -- jaxpr walking -----------------------------------------------------

    def read(self, env, v):
        if isinstance(v, Literal):
            return self.val(getattr(v.aval, "shape", ()), "const",
                            const=v.val)
        return env[v]

    def process_jaxpr(self, jaxpr, consts, args: List[_Val]) -> List[_Val]:
        env: Dict[Any, _Val] = {}
        for cv, cval in zip(jaxpr.constvars, consts):
            env[cv] = self.val(getattr(cv.aval, "shape", ()), "const",
                               const=np.asarray(cval))
        if len(jaxpr.invars) != len(args):
            raise ExtractError(
                f"arity mismatch: jaxpr has {len(jaxpr.invars)} inputs, "
                f"{len(args)} provided")
        for iv, a in zip(jaxpr.invars, args):
            env[iv] = a
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "add_any":
                # cotangent accumulation: semantically a plain add
                prim = "add"
            ins = [self.read(env, v) for v in eqn.invars]
            if prim in INLINE_PRIMS:
                sub = None
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    if key in eqn.params:
                        sub = eqn.params[key]
                        break
                if sub is None:
                    raise ExtractError(f"cannot inline '{prim}': no jaxpr "
                                       f"param")
                inner = getattr(sub, "jaxpr", sub)
                sub_consts = list(getattr(sub, "consts", ()))
                outs = self.process_jaxpr(inner, sub_consts, ins)
                for ov, o in zip(eqn.outvars, outs):
                    env[ov] = o
                continue
            if prim in ALIAS_PRIMS:
                env[eqn.outvars[0]] = ins[0]
                continue
            if prim == "broadcast_in_dim":
                env[eqn.outvars[0]] = self.broadcast(
                    ins[0], eqn.outvars[0].aval.shape,
                    eqn.params["broadcast_dimensions"])
                continue
            if prim in ("reshape", "squeeze", "expand_dims"):
                out_shape = tuple(eqn.outvars[0].aval.shape)
                in_shape = ins[0].shape
                if (in_shape and out_shape
                        and in_shape[-1] == out_shape[-1]
                        and math.prod(in_shape) == math.prod(out_shape)):
                    # trailing axis preserved: same row tensor
                    env[eqn.outvars[0]] = self.val(out_shape, "op",
                                                   base=ins[0],
                                                   bkind="trail")
                    continue
                if (in_shape and out_shape == in_shape
                        + (1,) * (len(out_shape) - len(in_shape))):
                    # appended size-1 axes: a keepdims expansion
                    env[eqn.outvars[0]] = self.val(out_shape, "op",
                                                   base=ins[0],
                                                   bkind="keep")
                    continue
                if (out_shape and in_shape == out_shape
                        + (1,) * (len(in_shape) - len(out_shape))):
                    # dropped trailing size-1 axes: pure alias (VJP
                    # graphs squeeze a keepdims stat before
                    # re-broadcasting it along the row)
                    env[eqn.outvars[0]] = self.val(out_shape, "op",
                                                   base=ins[0])
                    continue
            if prim in ("reduce_sum", "reduce_max", "reduce_min",
                        "reduce_prod"):
                axes = tuple(int(a) for a in eqn.params.get("axes", ()))
                if axes and ins[0].shape and \
                        all(ins[0].shape[a] == 1 for a in axes):
                    # reducing size-1 axes moves no data: pure alias
                    env[eqn.outvars[0]] = self.val(
                        eqn.outvars[0].aval.shape, "op", base=ins[0])
                    continue
            if prim == "integer_pow" and int(eqn.params.get("y", 0)) == 2:
                env[eqn.outvars[0]] = self.emit(
                    "square", ins, eqn.outvars[0].aval.shape, {})
                continue
            keep_params = {}
            if prim in ("reduce_sum", "reduce_max", "reduce_min",
                        "reduce_prod"):
                keep_params["axes"] = tuple(eqn.params.get("axes", ()))
            if prim == "integer_pow":
                keep_params["y"] = int(eqn.params.get("y", 0))
            if prim == "transpose":
                keep_params["permutation"] = tuple(
                    int(p) for p in eqn.params.get("permutation", ()))
            if prim == "dot_general":
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                keep_params["dimension_numbers"] = (
                    (tuple(int(a) for a in lc), tuple(int(a) for a in rc)),
                    (tuple(int(a) for a in lb), tuple(int(a) for a in rb)))
            out = self.emit(prim, ins, eqn.outvars[0].aval.shape,
                            keep_params)
            env[eqn.outvars[0]] = out
            for extra in eqn.outvars[1:]:
                # multi-output primitive (scan, while, ...): opaque barrier
                # per output
                env[extra] = self.emit(prim, ins, extra.aval.shape,
                                       keep_params)
        return [self.read(env, v) for v in jaxpr.outvars]


# --------------------------------------------------------------------------
# Composite recognition
# --------------------------------------------------------------------------

def _use_counts(eqns: List[_Eqn], outputs: List[_Val]) -> Dict[int, int]:
    counts: Dict[int, int] = {}
    for e in eqns:
        for v in e.ins:
            b = _base(v)
            counts[b.vid] = counts.get(b.vid, 0) + 1
    for v in outputs:
        b = _base(v)
        counts[b.vid] = counts.get(b.vid, 0) + 1
    return counts


class _Rewriter:
    """Fixpoint composite recognizer over the normalized eqn list."""

    def __init__(self, eqns: List[_Eqn], outputs: List[_Val]):
        self.eqns = eqns
        self.outputs = outputs
        self._synth = -2000            # fresh vids for rewrite-built vals

    def _prod(self) -> Dict[int, int]:
        return {_base(e.out).vid: i for i, e in enumerate(self.eqns)}

    def _producer(self, prod, v: _Val, prim: str,
                  strip: Tuple[str, ...] = ("keep", "row")) -> \
            Optional[_Eqn]:
        """The eqn producing ``v`` (looking through the given broadcast
        kinds) when its primitive is ``prim``."""
        b = v
        while b.base is not None and b.bkind in strip:
            b = b.base
        b = _base(b) if b.bkind == "" and b.base is not None else b
        if b.base is not None:          # unexpected broadcast kind left
            return None
        i = prod.get(b.vid)
        if i is None:
            return None
        e = self.eqns[i]
        return e if e.prim == prim else None

    def _last_axis(self, e: _Eqn) -> bool:
        axes = e.params.get("axes", ())
        nd = len(e.ins[0].shape)
        return tuple(axes) == (nd - 1,)

    def _replace(self, anchor: _Eqn, dead: List[_Eqn], prim: str,
                 ins: List[_Val], counts,
                 params: Optional[Dict[str, Any]] = None) -> bool:
        """Collapse ``dead + [anchor]`` into one composite at the anchor's
        position, iff every dead eqn's output is used only inside the
        pattern.  ``params`` carries recipe-relevant values recovered from
        the pattern (e.g. a norm's traced eps)."""
        new = _Eqn(prim, list(ins), anchor.out, dict(params or {}))
        return self._replace_multi(anchor, dead, [new], counts)

    def _replace_multi(self, anchor: _Eqn, dead: List[_Eqn],
                       new_eqns: List[_Eqn], counts) -> bool:
        """Like ``_replace`` but splices a short sequence of eqns at the
        anchor's position (used when a composite match leaves residue, e.g.
        a residual add wrapped around a matched backward body)."""
        in_pattern = {id(anchor)} | {id(d) for d in dead}
        for d in dead:
            uses = counts.get(_base(d.out).vid, 0)
            internal = sum(1 for e in self.eqns if id(e) in in_pattern
                           for v in e.ins if _base(v).vid ==
                           _base(d.out).vid)
            if uses != internal:
                return False
        out: List[_Eqn] = []
        for e in self.eqns:
            if e is anchor:
                out.extend(new_eqns)
            elif id(e) in in_pattern:
                continue
            else:
                out.append(e)
        self.eqns[:] = out
        return True

    def _rewrap(self, v: _Val, new_base: _Val) -> _Val:
        """A value shaped like ``v`` but aliasing ``new_base`` through the
        same broadcast kind (used when a rewrite looks through a broadcast
        and must re-wrap a different underlying tensor)."""
        if v.base is None or not v.bkind:
            return new_base
        self._synth -= 1
        return _Val(self._synth, v.shape, "op", base=new_base,
                    bkind=v.bkind)

    # -- individual patterns ----------------------------------------------

    def _match_recip_mul(self, e: _Eqn, prod, counts) -> bool:
        # mul(x, bcast(div(1, s))) -> div(x, bcast(s)): the transposed
        # form of a row divide (VJP graphs multiply by a broadcast
        # reciprocal); normalizing it back to div lets the softmax
        # matcher recognize backward-traced softmax bodies
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            dv = self._producer(prod, e.ins[i], "div")
            if dv is None or _scalar_const(dv.ins[0]) != 1.0:
                continue
            s = dv.ins[1]
            if _base(s).kind == "const":
                continue
            wrap = self._rewrap(e.ins[i], s)
            return self._replace(e, [dv], "div", [e.ins[j], wrap], counts)
        return False

    def _match_relu(self, e: _Eqn, prod, counts) -> bool:
        if e.prim != "max" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            if _scalar_const(e.ins[i]) == 0.0 and \
                    _base(e.ins[j]).kind != "const":
                return self._replace(e, [], "relu", [e.ins[j]], counts)
        return False

    def _match_silu(self, e: _Eqn, prod, counts) -> bool:
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            sig = self._producer(prod, e.ins[i], "logistic")
            if sig is not None and \
                    _base(sig.ins[0]).vid == _base(e.ins[j]).vid:
                return self._replace(e, [sig], "silu", [e.ins[j]], counts)
        return False

    def _match_swiglu(self, e: _Eqn, prod, counts) -> bool:
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            s = self._producer(prod, e.ins[i], "silu")
            if s is not None and _base(e.ins[j]).kind != "const":
                return self._replace(e, [s], "swiglu",
                                     [s.ins[0], e.ins[j]], counts)
        return False

    def _const_mul(self, prod, v: _Val, want: float) -> Optional[_Val]:
        """v == mul(c≈want, x) -> x (either operand order)."""
        m = self._producer(prod, v, "mul")
        if m is None:
            return None
        for i, j in ((0, 1), (1, 0)):
            c = _scalar_const(m.ins[i])
            if c is not None and _isclose(c, want):
                return m.ins[j]
        return None

    def _match_gelu_tanh(self, e: _Eqn, prod, counts) -> bool:
        # x * (0.5 * (1 + tanh(0.79788 * (x + 0.044715 * x^3))))
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            x, h = e.ins[i], e.ins[j]
            if _base(x).kind == "const":
                continue
            hm = self._producer(prod, h, "mul")
            if hm is None:
                continue
            half = None
            for a, b in ((0, 1), (1, 0)):
                if _scalar_const(hm.ins[a]) == 0.5:
                    half = hm.ins[b]
            if half is None:
                continue
            g = self._producer(prod, half, "add")
            if g is None:
                continue
            f = None
            for a, b in ((0, 1), (1, 0)):
                if _scalar_const(g.ins[a]) == 1.0:
                    f = self._producer(prod, g.ins[b], "tanh")
            if f is None:
                continue
            em = self._producer(prod, f.ins[0], "mul")
            if em is None:
                continue
            d = None
            for a, b in ((0, 1), (1, 0)):
                c = _scalar_const(em.ins[a])
                if c is not None and _isclose(c, math.sqrt(2.0 / math.pi)):
                    d = self._producer(prod, em.ins[b], "add")
            if d is None:
                continue
            cm = cube = None
            for a, b in ((0, 1), (1, 0)):
                if _base(d.ins[a]).vid != _base(x).vid:
                    continue
                cm2 = self._producer(prod, d.ins[b], "mul")
                if cm2 is None:
                    continue
                for p, q in ((0, 1), (1, 0)):
                    c2 = _scalar_const(cm2.ins[p])
                    if c2 is None or not _isclose(c2, 0.044715):
                        continue
                    pw = self._producer(prod, cm2.ins[q], "integer_pow")
                    if pw is not None and pw.params.get("y") == 3 and \
                            _base(pw.ins[0]).vid == _base(x).vid:
                        cm, cube = cm2, pw
            if cube is None:
                continue
            return self._replace(e, [hm, g, f, em, d, cm, cube], "gelu",
                                 [x], counts)
        return False

    def _match_gelu_erf(self, e: _Eqn, prod, counts) -> bool:
        # exact gelu, erfc form: (0.5 * x) * erfc(-x * 0.70710)
        # and erf form:          (0.5 * x) * (1 + erf(x * 0.70710))
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        inv_sqrt2 = 1.0 / math.sqrt(2.0)
        for i, j in ((0, 1), (1, 0)):
            halfx = self._const_mul(prod, e.ins[i], 0.5)
            bm = self._producer(prod, e.ins[i], "mul")
            if halfx is None or bm is None or \
                    _base(halfx).kind == "const":
                continue
            x = _base(halfx)
            other = e.ins[j]
            ec = self._producer(prod, other, "erfc")
            if ec is not None:
                negx = self._const_mul(prod, ec.ins[0], inv_sqrt2)
                dm = self._producer(prod, ec.ins[0], "mul")
                if negx is not None and dm is not None:
                    ng = self._producer(prod, negx, "neg")
                    if ng is not None and _base(ng.ins[0]).vid == x.vid:
                        return self._replace(e, [bm, ec, dm, ng], "gelu",
                                             [halfx], counts)
            g = self._producer(prod, other, "add")
            if g is not None:
                for a, b in ((0, 1), (1, 0)):
                    if _scalar_const(g.ins[a]) != 1.0:
                        continue
                    ef = self._producer(prod, g.ins[b], "erf")
                    if ef is None:
                        continue
                    xe = self._const_mul(prod, ef.ins[0], inv_sqrt2)
                    dm = self._producer(prod, ef.ins[0], "mul")
                    if xe is not None and dm is not None and \
                            _base(xe).vid == x.vid:
                        return self._replace(e, [bm, g, ef, dm], "gelu",
                                             [halfx], counts)
        return False

    def _match_softmax(self, e: _Eqn, prod, counts) -> bool:
        # div(exp(x - max_row(x)), sum_row(exp(x - max_row(x))))
        if e.prim != "div" or len(e.ins) != 2:
            return False
        rs = self._producer(prod, e.ins[1], "reduce_sum")
        if rs is None or not self._last_axis(rs):
            return False
        if _base(rs.ins[0]).vid != _base(e.ins[0]).vid:
            return False
        ex = self._producer(prod, e.ins[0], "exp")
        if ex is None:
            return False
        sb = self._producer(prod, ex.ins[0], "sub")
        if sb is None:
            return False
        x = sb.ins[0]
        rm = self._producer(prod, sb.ins[1], "reduce_max")
        if rm is None or not self._last_axis(rm):
            return False
        if _base(rm.ins[0]).vid != _base(x).vid:
            return False
        return self._replace(e, [rs, ex, sb, rm], "softmax", [x], counts)

    def _match_log_softmax(self, e: _Eqn, prod, counts) -> bool:
        # sub(shifted, log(sum(exp(shifted))))  with
        # shifted = sub(x, max_row(x))           [jax.nn.log_softmax]
        if e.prim != "sub" or len(e.ins) != 2:
            return False
        lg = self._producer(prod, e.ins[1], "log")
        if lg is None:
            return False
        rs = self._producer(prod, lg.ins[0], "reduce_sum")
        if rs is None or not self._last_axis(rs):
            return False
        ex = self._producer(prod, rs.ins[0], "exp")
        if ex is None:
            return False
        if _base(ex.ins[0]).vid != _base(e.ins[0]).vid:
            return False
        sb = self._producer(prod, e.ins[0], "sub")
        if sb is None:
            return False
        x = sb.ins[0]
        rm = self._producer(prod, sb.ins[1], "reduce_max")
        if rm is None or not self._last_axis(rm):
            return False
        if _base(rm.ins[0]).vid != _base(x).vid:
            return False
        return self._replace(e, [lg, rs, ex, sb, rm], "log_softmax", [x],
                             counts)

    def _match_log_softmax_bwd(self, e: _Eqn, prod, counts) -> bool:
        # dz of log_softmax, as the transposed jaxpr emits it:
        #     dz = g + softmax(z) * rowsum(-g)
        # spelled  add(g, mul(row(div(rowsum(neg(g)), s)), e))  with
        # e = exp(z - max_row(z)), s = rowsum(e).  The cotangent-side
        # numerator rides INSIDE the softmax divide, so the forward
        # softmax matcher can never claim this graph.
        if e.prim != "add" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            g_v = e.ins[j]
            if _base(g_v).kind == "const":
                continue
            m = self._producer(prod, e.ins[i], "mul")
            if m is None:
                continue
            e_full, stat = self._split_rowstat(m)
            if e_full is None or stat is None:
                continue
            ex = self._producer(prod, e_full, "exp")
            if ex is None:
                continue
            sb = self._producer(prod, ex.ins[0], "sub")
            if sb is None:
                continue
            z = sb.ins[0]
            rm = self._producer(prod, sb.ins[1], "reduce_max")
            if rm is None or not self._last_axis(rm) or \
                    _base(rm.ins[0]).vid != _base(z).vid:
                continue
            dv = self._producer(prod, stat, "div")
            if dv is None or len(dv.ins) != 2:
                continue
            rs_e = self._producer(prod, dv.ins[1], "reduce_sum")
            if rs_e is None or not self._last_axis(rs_e) or \
                    _base(rs_e.ins[0]).vid != _base(e_full).vid:
                continue
            rs_g = self._producer(prod, dv.ins[0], "reduce_sum")
            if rs_g is None or not self._last_axis(rs_g):
                continue
            ng = self._producer(prod, rs_g.ins[0], "neg")
            if ng is None or _base(ng.ins[0]).vid != _base(g_v).vid:
                continue
            return self._replace(e, [m, ex, sb, rm, dv, rs_e, rs_g, ng],
                                 "log_softmax_bwd", [z, g_v], counts)
        return False

    def _match_softmax_bwd(self, e: _Eqn, prod, counts) -> bool:
        # dz of softmax:  dz = y * (g - rowsum(g * y)),  y = softmax(z).
        # The transposed jaxpr spells it
        #     mul(add(div(g, s), row(neg(rowsum(mul(mul(g, s^-2), e))))), e)
        # with e = exp(z - max_row(z)), s = rowsum(e)  (the s^-2 factor is
        # the transposed quotient rule folded into one integer_pow).
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            ex = self._producer(prod, e.ins[i], "exp")
            if ex is None:
                continue
            sb = self._producer(prod, ex.ins[0], "sub")
            if sb is None:
                continue
            z = sb.ins[0]
            rm = self._producer(prod, sb.ins[1], "reduce_max")
            if rm is None or not self._last_axis(rm) or \
                    _base(rm.ins[0]).vid != _base(z).vid:
                continue
            ad = self._producer(prod, e.ins[j], "add")
            if ad is None or len(ad.ins) != 2:
                continue
            for p, q in ((0, 1), (1, 0)):
                dv = self._producer(prod, ad.ins[p], "div")
                if dv is None:
                    continue
                g_v = dv.ins[0]
                if _base(g_v).kind == "const":
                    continue
                rs_e = self._producer(prod, dv.ins[1], "reduce_sum")
                if rs_e is None or not self._last_axis(rs_e) or \
                        _base(rs_e.ins[0]).vid != _base(ex.out).vid:
                    continue
                ng = self._producer(prod, ad.ins[q], "neg")
                if ng is None:
                    continue
                rs_t = self._producer(prod, ng.ins[0], "reduce_sum")
                if rs_t is None or not self._last_axis(rs_t):
                    continue
                pm = self._producer(prod, rs_t.ins[0], "mul")
                if pm is None or len(pm.ins) != 2:
                    continue
                # mul(mul(g, s^-2), e) in either association
                gm = ip = None
                for a, b_ in ((0, 1), (1, 0)):
                    if _base(pm.ins[a]).vid == _base(ex.out).vid:
                        gm = self._producer(prod, pm.ins[b_], "mul")
                if gm is None or len(gm.ins) != 2:
                    continue
                for a, b_ in ((0, 1), (1, 0)):
                    cand = self._producer(prod, gm.ins[a], "integer_pow")
                    if cand is not None and \
                            cand.params.get("y") == -2 and \
                            _base(gm.ins[b_]).vid == _base(g_v).vid:
                        ip = cand
                if ip is None or \
                        _base(ip.ins[0]).vid != _base(rs_e.out).vid:
                    continue
                return self._replace(
                    e, [ex, sb, rm, ad, dv, rs_e, ng, rs_t, pm, gm, ip],
                    "softmax_bwd", [z, g_v], counts)
        return False

    def _mean_of(self, prod, v: _Val,
                 n_cols: int) -> Tuple[Optional[_Eqn], List[_Eqn]]:
        """Match ``v == mean(u, -1)`` in either lowering — ``sum(u)/C`` or
        ``sum(u) * (1/C)`` — returning the reduce_sum eqn and the dead
        mean arithmetic."""
        dv = self._producer(prod, v, "div")
        if dv is not None and _scalar_const(dv.ins[1]) == float(n_cols):
            rs = self._producer(prod, dv.ins[0], "reduce_sum")
            if rs is not None and self._last_axis(rs):
                return rs, [dv]
        mm = self._const_mul(prod, v, 1.0 / n_cols)
        if mm is not None:
            rs = self._producer(prod, mm, "reduce_sum")
            if rs is not None and self._last_axis(rs):
                return rs, [self._producer(prod, v, "mul")]
        return None, []

    def _match_layernorm(self, e: _Eqn, prod, counts) -> bool:
        # ((x - mu) * rsqrt(var + eps)) * w + b   [w, b trailing vectors;
        # mu = mean(x), var = mean((x - mu)^2); the centering sub may be
        # CSE-duplicated in the jaxpr — both copies must match]
        if e.prim != "add" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            b_v = e.ins[i]
            bb = _base(b_v)
            if not (b_v.bkind == "trail" and len(bb.shape) == 1
                    and bb.kind != "const"):
                continue
            q = self._producer(prod, e.ins[j], "mul")
            if q is None:
                continue
            for a1, a2 in ((0, 1), (1, 0)):
                w_v = q.ins[a1]
                wb = _base(w_v)
                if not (w_v.bkind == "trail" and len(wb.shape) == 1
                        and wb.kind != "const"):
                    continue
                o = self._producer(prod, q.ins[a2], "mul")
                if o is None:
                    continue
                for p1, p2 in ((0, 1), (1, 0)):
                    cent = self._producer(prod, o.ins[p1], "sub")
                    rq = self._producer(prod, o.ins[p2], "rsqrt")
                    if cent is None or rq is None:
                        continue
                    x, mu_v = cent.ins[0], cent.ins[1]
                    if _base(x).kind == "const" or len(_base(x).shape) < 2:
                        continue
                    n_cols = _base(x).shape[-1]
                    mu_rs, mu_dead = self._mean_of(prod, mu_v, n_cols)
                    if mu_rs is None or \
                            _base(mu_rs.ins[0]).vid != _base(x).vid:
                        continue
                    ad = self._producer(prod, rq.ins[0], "add")
                    if ad is None:
                        continue
                    eps = None
                    var_v = None
                    for c1, c2 in ((0, 1), (1, 0)):
                        c = _scalar_const(ad.ins[c1])
                        if c is not None and 0 < c < 1e-3:
                            eps, var_v = c, ad.ins[c2]
                    if var_v is None:
                        continue
                    var_rs, var_dead = self._mean_of(prod, var_v, n_cols)
                    if var_rs is None:
                        continue
                    sq = self._producer(prod, var_rs.ins[0], "square")
                    if sq is None:
                        mq = self._producer(prod, var_rs.ins[0], "mul")
                        if mq is None or _base(mq.ins[0]).vid != \
                                _base(mq.ins[1]).vid:
                            continue
                        sq = mq
                    c2e = self._producer(prod, sq.ins[0], "sub")
                    if c2e is None:
                        continue
                    if (_base(c2e.ins[0]).vid != _base(x).vid
                            or _base(c2e.ins[1]).vid != _base(mu_v).vid):
                        continue
                    dead_ids = {}
                    for d in ([q, o, cent, rq, ad, var_rs, sq, c2e, mu_rs]
                              + mu_dead + var_dead):
                        dead_ids[id(d)] = d
                    dead_ids.pop(id(e), None)
                    return self._replace(e, list(dead_ids.values()),
                                         "layernorm", [x, w_v, b_v],
                                         counts, params={"eps": float(eps)})
        return False

    def _match_rmsnorm(self, e: _Eqn, prod, counts) -> bool:
        # (x * rsqrt(mean(x*x, -1) + eps)) * w    [w: trailing vector]
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for i, j in ((0, 1), (1, 0)):
            w = e.ins[i]
            wb = _base(w)
            if not (w.bkind == "trail" and len(wb.shape) == 1
                    and wb.kind != "const"):
                continue
            im = self._producer(prod, e.ins[j], "mul")
            if im is None:
                continue
            for a, b in ((0, 1), (1, 0)):
                x = im.ins[a]
                if _base(x).kind == "const":
                    continue
                rq = self._producer(prod, im.ins[b], "rsqrt")
                if rq is None:
                    continue
                ad = self._producer(prod, rq.ins[0], "add")
                if ad is None:
                    continue
                eps = None
                mean_v = None
                for p, q in ((0, 1), (1, 0)):
                    c = _scalar_const(ad.ins[p])
                    if c is not None and 0 < c < 1e-3:
                        eps, mean_v = c, ad.ins[q]
                if mean_v is None:
                    continue
                # any small eps matches; the traced value rides the
                # composite's params into the chain's recipe attrs
                n_cols = _base(x).shape[-1]
                dv = self._producer(prod, mean_v, "div")
                ss_v = None
                dead_mean = []
                if dv is not None and \
                        _scalar_const(dv.ins[1]) == float(n_cols):
                    ss_v, dead_mean = dv.ins[0], [dv]
                else:
                    mm = self._const_mul(prod, mean_v, 1.0 / n_cols)
                    if mm is not None:
                        ss_v = mm
                        dead_mean = [self._producer(prod, mean_v, "mul")]
                if ss_v is None:
                    continue
                rs = self._producer(prod, ss_v, "reduce_sum")
                if rs is None or not self._last_axis(rs):
                    continue
                sq = None
                sq_e = self._producer(prod, rs.ins[0], "square")
                if sq_e is not None and \
                        _base(sq_e.ins[0]).vid == _base(x).vid:
                    sq = sq_e
                else:
                    mq = self._producer(prod, rs.ins[0], "mul")
                    if mq is not None and \
                            _base(mq.ins[0]).vid == _base(x).vid and \
                            _base(mq.ins[1]).vid == _base(x).vid:
                        sq = mq
                if sq is None:
                    continue
                dead = [im, rq, ad, rs, sq] + dead_mean
                return self._replace(e, dead, "rmsnorm", [x, w], counts,
                                     params={"eps": float(eps)})
        return False

    def _split_rowstat(self, m: _Eqn) -> Tuple[Optional[_Val],
                                               Optional[_Val]]:
        """Split a binary mul into (full-row operand, per-row stat
        operand) — the stat side is a keepdims (R,1) value or a row
        re-broadcast of an (R,) value."""
        if len(m.ins) != 2:
            return None, None
        a0, a1 = m.ins
        ok0 = _operand_ok(a0, m.out.shape)
        ok1 = _operand_ok(a1, m.out.shape)
        if ok0 and not ok1:
            return a0, a1
        if ok1 and not ok0:
            return a1, a0
        return None, None

    def _match_rmsnorm_bwd(self, e: _Eqn, prod, counts) -> bool:
        # dx of weighted rmsnorm, exactly as the transposed jaxpr emits
        # it (three-term add tree; h = mean(x^2)+eps, i = rsqrt(h),
        # n = g*w, s = sum(x*n, -1), v = s * (-0.5 * i/h) / N):
        #     dx = n*i + x*v + v*x
        if e.prim != "add" or len(e.ins) != 2:
            return False
        # Flatten the whole same-shape add tree rooted at the anchor: the
        # three backward terms may be interleaved with residue terms (the
        # residual cotangent in vjp(x + norm(x)) lands INSIDE the tree, so
        # no 3-term subtree exists).  Residue terms are re-materialized as
        # adds around the matched composite.
        terms: List[_Val] = []
        tree: List[_Eqn] = []
        stack = [e.ins[0], e.ins[1]]
        while stack:
            v = stack.pop()
            sub = self._producer(prod, v, "add")
            if sub is not None and len(sub.ins) == 2 and \
                    sub.out.shape == e.out.shape:
                tree.append(sub)
                stack.extend(sub.ins)
            else:
                terms.append(v)
        if len(terms) < 3:
            return False
        for _once in (0,):
            ni_m = None
            xv_cands = []   # (term, mul eqn) candidates for the x*v pair
            extras = []     # residue terms, re-added around the composite
            for t in terms:
                m = self._producer(prod, t, "mul")
                if m is None:
                    extras.append(t)
                    continue
                _, stat = self._split_rowstat(m)
                if ni_m is None and stat is not None and \
                        self._producer(prod, stat, "rsqrt") is not None:
                    ni_m = m
                else:
                    xv_cands.append((t, m))
            if ni_m is None or len(xv_cands) < 2:
                continue
            # the two symmetric x*v terms share one x and one v base
            xv_ms = None
            for a in range(len(xv_cands)):
                for b in range(a + 1, len(xv_cands)):
                    m1, m2 = xv_cands[a][1], xv_cands[b][1]
                    xa, va = self._split_rowstat(m1)
                    xb, vb = self._split_rowstat(m2)
                    if xa is not None and xb is not None and \
                            _base(xa).vid == _base(xb).vid and \
                            _base(va).vid == _base(vb).vid:
                        xv_ms = [m1, m2]
                        extras.extend(t for k, (t, _m) in
                                      enumerate(xv_cands) if k not in (a, b))
                        break
                if xv_ms is not None:
                    break
            if xv_ms is None:
                continue
            n_v, i_v = self._split_rowstat(ni_m)
            if n_v is None:
                continue
            i_rq = self._producer(prod, i_v, "rsqrt")
            # n = g * w  (w a trailing-broadcast learned gain)
            nm = self._producer(prod, n_v, "mul")
            if nm is None or len(nm.ins) != 2:
                continue
            w_v = g_v = None
            for a, b_ in ((0, 1), (1, 0)):
                cand = nm.ins[a]
                if cand.bkind == "trail" and len(_base(cand).shape) == 1 \
                        and _base(cand).kind != "const":
                    w_v, g_v = cand, nm.ins[b_]
            if w_v is None or _base(g_v).kind == "const":
                continue
            # the two symmetric x*v terms share x and v
            x1, v1 = self._split_rowstat(xv_ms[0])
            x2, v2 = self._split_rowstat(xv_ms[1])
            if x1 is None or x2 is None or \
                    _base(x1).vid != _base(x2).vid or \
                    _base(v1).vid != _base(v2).vid:
                continue
            x_v = x1
            if _base(x_v).kind == "const" or len(_base(x_v).shape) < 2:
                continue
            n_cols = _base(x_v).shape[-1]
            # v = (s * k) / N   (either mean lowering)
            dv = self._producer(prod, v1, "div")
            sk_v = None
            dead_vmean: List[_Eqn] = []
            if dv is not None and \
                    _scalar_const(dv.ins[1]) == float(n_cols):
                sk_v, dead_vmean = dv.ins[0], [dv]
            else:
                mm = self._const_mul(prod, v1, 1.0 / n_cols)
                if mm is not None:
                    sk_v = mm
                    dead_vmean = [self._producer(prod, v1, "mul")]
            if sk_v is None:
                continue
            sk = self._producer(prod, sk_v, "mul")
            if sk is None or len(sk.ins) != 2:
                continue
            s_rs = k_v = None
            for a, b_ in ((0, 1), (1, 0)):
                rs_c = self._producer(prod, sk.ins[a], "reduce_sum")
                if rs_c is not None and self._last_axis(rs_c):
                    s_rs, k_v = rs_c, sk.ins[b_]
            if s_rs is None:
                continue
            # s = sum(x * n, -1)
            pm = self._producer(prod, s_rs.ins[0], "mul")
            if pm is None or len(pm.ins) != 2:
                continue
            pv = {_base(pm.ins[0]).vid, _base(pm.ins[1]).vid}
            if pv != {_base(x_v).vid, _base(n_v).vid}:
                continue
            # k = -0.5 * (i / h)
            ih_v = self._const_mul(prod, k_v, -0.5)
            if ih_v is None:
                continue
            k_m = self._producer(prod, k_v, "mul")
            ih = self._producer(prod, ih_v, "div")
            if ih is None or len(ih.ins) != 2:
                continue
            if _base(ih.ins[0]).vid != _base(i_v).vid:
                continue
            h_v = ih.ins[1]
            if _base(h_v).vid != _base(i_rq.ins[0]).vid:
                continue
            # h = mean(x^2, -1) + eps
            ad = self._producer(prod, i_rq.ins[0], "add")
            if ad is None:
                continue
            eps = mean_v = None
            for p, q in ((0, 1), (1, 0)):
                c = _scalar_const(ad.ins[p])
                if c is not None and 0 < c < 1e-3:
                    eps, mean_v = c, ad.ins[q]
            if mean_v is None:
                continue
            mu_rs, mu_dead = self._mean_of(prod, mean_v, n_cols)
            if mu_rs is None:
                continue
            sq = self._producer(prod, mu_rs.ins[0], "square")
            if sq is not None and _base(sq.ins[0]).vid != _base(x_v).vid:
                sq = None
            if sq is None:
                mq = self._producer(prod, mu_rs.ins[0], "mul")
                if mq is not None and \
                        _base(mq.ins[0]).vid == _base(x_v).vid and \
                        _base(mq.ins[1]).vid == _base(x_v).vid:
                    sq = mq
            if sq is None:
                continue
            dead_ids: Dict[int, _Eqn] = {}
            for d in (tree + [ni_m, xv_ms[0], xv_ms[1], nm, i_rq, ih,
                              k_m, sk, s_rs, pm, ad, mu_rs, sq]
                      + dead_vmean + mu_dead):
                dead_ids[id(d)] = d
            dead_ids.pop(id(e), None)
            if not extras:
                return self._replace(e, list(dead_ids.values()),
                                     "rmsnorm_bwd", [x_v, w_v, g_v], counts,
                                     params={"eps": float(eps)})
            # residual form: splice the composite plus adds that restore
            # the residue terms the tree carried around it
            new_eqns: List[_Eqn] = []
            self._synth -= 1
            acc = _Val(self._synth, e.out.shape, "op")
            new_eqns.append(_Eqn("rmsnorm_bwd", [x_v, w_v, g_v], acc,
                                 {"eps": float(eps)}))
            for k, ex in enumerate(extras):
                if k == len(extras) - 1:
                    nxt = e.out
                else:
                    self._synth -= 1
                    nxt = _Val(self._synth, e.out.shape, "op")
                new_eqns.append(_Eqn("add", [ex, acc], nxt, {}))
                acc = nxt
            if self._replace_multi(e, list(dead_ids.values()), new_eqns,
                                   counts):
                return True
        return False

    def _match_rmsnorm_noweight(self, e: _Eqn, prod, counts) -> bool:
        # x * rsqrt(mean(x*x, -1) + eps)    [no learned gain]
        #
        # Registered after the weighted rmsnorm and layernorm matchers so a
        # full affine pattern is always collapsed before this one can claim
        # its inner normalization mul.
        if e.prim != "mul" or len(e.ins) != 2:
            return False
        for a, b in ((0, 1), (1, 0)):
            x = e.ins[a]
            if _base(x).kind == "const" or len(_base(x).shape) < 2:
                continue
            rq = self._producer(prod, e.ins[b], "rsqrt")
            if rq is None:
                continue
            ad = self._producer(prod, rq.ins[0], "add")
            if ad is None:
                continue
            eps = None
            mean_v = None
            for p, q in ((0, 1), (1, 0)):
                c = _scalar_const(ad.ins[p])
                if c is not None and 0 < c < 1e-3:
                    eps, mean_v = c, ad.ins[q]
            if mean_v is None:
                continue
            n_cols = _base(x).shape[-1]
            dv = self._producer(prod, mean_v, "div")
            ss_v = None
            dead_mean = []
            if dv is not None and \
                    _scalar_const(dv.ins[1]) == float(n_cols):
                ss_v, dead_mean = dv.ins[0], [dv]
            else:
                mm = self._const_mul(prod, mean_v, 1.0 / n_cols)
                if mm is not None:
                    ss_v = mm
                    dead_mean = [self._producer(prod, mean_v, "mul")]
            if ss_v is None:
                continue
            rs = self._producer(prod, ss_v, "reduce_sum")
            if rs is None or not self._last_axis(rs):
                continue
            sq = None
            sq_e = self._producer(prod, rs.ins[0], "square")
            if sq_e is not None and \
                    _base(sq_e.ins[0]).vid == _base(x).vid:
                sq = sq_e
            else:
                mq = self._producer(prod, rs.ins[0], "mul")
                if mq is not None and \
                        _base(mq.ins[0]).vid == _base(x).vid and \
                        _base(mq.ins[1]).vid == _base(x).vid:
                    sq = mq
            if sq is None:
                continue
            dead = [rq, ad, rs, sq] + dead_mean
            return self._replace(e, dead, "rmsnorm", [x], counts,
                                 params={"eps": float(eps)})
        return False

    def _dot_as_matmul(self, d: _Eqn):
        """Classify a dot_general as a per-slice row matmul.

        Returns a list of candidate ``(R, W, op, wf_out)`` tuples — the row
        tensor, the weight tensor, the stage op ("matmul" contracts W's
        leading per-slice axis, i.e. rows @ W; "matmul_t" its trailing,
        i.e. rows @ W.T) and the output axis carrying W's free dimension.
        An orientation is dropped when the contraction does not fit the
        template: multiple contracting pairs, no batch dims (an unbatched
        ``h @ w`` stays a barrier), W with more than one free axis per
        slice, or a row tensor that does not contract its trailing axis.
        Both orientations can fit (single-token decode QK^T: q collapses
        to one free axis so it is template-shaped as either rows or
        weight); the caller picks the candidate whose output axis lands
        where it needs it.
        """
        dn = d.params.get("dimension_numbers")
        if dn is None or len(d.ins) != 2:
            return []
        (lc, rc), (lb, rb) = dn
        if len(lc) != 1 or len(rc) != 1:
            return []
        cands = []
        for r_i in (1, 0):               # traced attention puts rows on rhs
            w_i = 1 - r_i
            R, W = d.ins[r_i], d.ins[w_i]
            if any(_base(v).kind == "const" or len(_base(v).shape) < 2
                   for v in (R, W)):
                continue
            rsh, wsh = R.shape, W.shape
            r_c = (rc if r_i == 1 else lc)[0]
            w_c = (lc if r_i == 1 else rc)[0]
            r_b = rb if r_i == 1 else lb
            w_b = lb if r_i == 1 else rb
            if not r_b:
                continue
            if r_c != len(rsh) - 1:
                continue
            w_free = [ax for ax in range(len(wsh))
                      if ax not in w_b and ax != w_c]
            if len(w_free) != 1:
                continue
            op = "matmul" if w_c < w_free[0] else "matmul_t"
            nb = len(lb)
            lhs_free = len(d.ins[0].shape) - 1 - nb
            wf_out = nb if w_i == 0 else nb + lhs_free
            cands.append((R, W, op, wf_out))
        return cands

    def _match_matmul(self, e: _Eqn, prod, counts) -> bool:
        """dot_general (optionally followed by a transpose that puts the
        weight's free axis last) becomes a matmul / matmul_t stage eqn with
        ins ``[rows, weight]``.  Leading output axes may land in any order:
        rows are opaque to the chain machinery."""
        if e.prim == "dot_general":
            for R, W, op, wf_out in self._dot_as_matmul(e):
                if wf_out == len(e.out.shape) - 1:
                    return self._replace(e, [], op, [R, W], counts)
            return False
        if e.prim == "transpose":
            d = self._producer(prod, e.ins[0], "dot_general", strip=())
            if d is None:
                return False
            perm = e.params.get("permutation", ())
            for R, W, op, wf_out in self._dot_as_matmul(d):
                if perm and perm[-1] == wf_out:
                    return self._replace(e, [d], op, [R, W], counts)
            return False
        return False

    def _scale_pass(self) -> None:
        """Leftover multiplications by a traced scalar constant become
        'scale' stage eqns (the constant rides in params).  Runs after the
        composite fixpoint so const-mul-bearing composites (gelu, the mean
        inside a norm) are matched first."""
        for idx, e in enumerate(self.eqns):
            if e.prim != "mul" or len(e.ins) != 2:
                continue
            if len(e.out.shape) < 2:
                continue
            for i, j in ((0, 1), (1, 0)):
                c = _scalar_const(e.ins[i])
                t = e.ins[j]
                if c is None or _base(t).kind == "const":
                    continue
                self.eqns[idx] = _Eqn("scale", [t], e.out,
                                      {"scale": float(c)})
                break

    def _masked_fill_pass(self) -> bool:
        """where(pred, x, -big) feeding only softmax row inputs becomes
        add(x, mask) with a synthesized external mask input."""
        changed = False
        n_masks = sum(1 for e in self.eqns for v in e.ins
                      if _base(v).kind == "ext"
                      and _base(v).name.startswith("%mask"))
        for idx, e in enumerate(list(self.eqns)):
            if e.prim != "select_n" or len(e.ins) != 3:
                continue
            pred, case_f, case_t = e.ins
            x, fill = None, None
            cf, ct = _scalar_const(case_f), _scalar_const(case_t)
            if cf is not None and cf <= _BIG_NEG and \
                    _base(case_t).kind != "const":
                x, fill = case_t, cf
            elif ct is not None and ct <= _BIG_NEG and \
                    _base(case_f).kind != "const":
                x, fill = case_f, ct
            if x is None:
                continue
            consumers = [(c, k) for c in self.eqns if c is not e
                         for k, v in enumerate(c.ins)
                         if _base(v).vid == _base(e.out).vid]
            if not consumers or any(
                    c.prim not in ("softmax", "log_softmax") or k != 0
                    for c, k in consumers):
                continue
            if any(_base(o).vid == _base(e.out).vid
                   for o in self.outputs):
                continue
            mask = _Val(-(n_masks + 1000), tuple(e.out.shape), "ext",
                        name=f"%mask{n_masks}")
            n_masks += 1
            self.eqns[idx] = _Eqn("add", [x, mask], e.out, {})
            changed = True
        return changed

    def run(self) -> None:
        matchers = (self._match_recip_mul, self._match_relu,
                    self._match_silu,
                    self._match_gelu_tanh, self._match_gelu_erf,
                    self._match_softmax, self._match_log_softmax,
                    self._match_softmax_bwd,
                    self._match_log_softmax_bwd,
                    self._match_rmsnorm, self._match_layernorm,
                    self._match_swiglu, self._match_matmul,
                    self._match_rmsnorm_bwd,
                    self._match_rmsnorm_noweight)
        changed = True
        while changed:
            changed = False
            for m in matchers:
                counts = _use_counts(self.eqns, self.outputs)
                prod = self._prod()
                for e in list(self.eqns):
                    if e in self.eqns and m(e, prod, counts):
                        changed = True
                        counts = _use_counts(self.eqns, self.outputs)
                        prod = self._prod()
        while self._masked_fill_pass():
            pass
        self._scale_pass()


# --------------------------------------------------------------------------
# OpGraph emission
# --------------------------------------------------------------------------

def _crank(shape: Tuple[int, ...]) -> int:
    """Canonical rank: row tensors collapse to 2 (leading axes flatten into
    rows), vectors stay 1."""
    return min(len(shape), 2)


def _operand_ok(v: _Val, out_shape: Tuple[int, ...]) -> bool:
    """Chain-harness-expressible operand: a full row tensor (same shape as
    the result, canonical rank 2) or a trailing-broadcast vector/row block
    whose last axis matches the result's.  Keepdims expansions, scalar
    fills, consts and degenerate (size-1 trailing) broadcasts are not
    expressible and force the eqn to a barrier."""
    b = _base(v)
    if b.kind == "const" or not b.shape:
        return False
    if v.bkind == "trail":
        return b.shape[-1] == out_shape[-1]
    if v.bkind:
        return False
    return tuple(b.shape) == tuple(out_shape)


def _fusable_eqn(e: _Eqn) -> Optional[Tuple[str, List[_Val]]]:
    """(op, operands) when the eqn maps onto a proposer stage op with
    sound operand roles, else None (barrier)."""
    comps = ("softmax", "log_softmax", "rmsnorm", "layernorm", "gelu",
             "silu", "relu", "swiglu", "square", "tanh", "exp", "abs",
             "neg", "sqrt", "sigmoid", "scale", "matmul", "matmul_t",
             "rmsnorm_bwd", "softmax_bwd", "log_softmax_bwd")
    op = e.prim if e.prim in comps else PRIM_MAP.get(e.prim)
    if op is None:
        return None
    if len(e.out.shape) < 2:
        return None                      # rank-1 math cannot anchor a row
    ins = list(e.ins)
    if op == "mul" and len(ins) == 2:
        # tensor x traced rank-0 scalar -> 'smul' stage (the scalar rides
        # as a () input; VJP graphs of mixing layers scale whole streams
        # by scalar coefficients)
        for i, j in ((0, 1), (1, 0)):
            s, t = _base(ins[i]), ins[j]
            if (not s.shape and s.kind != "const"
                    and ins[i].bkind in ("", "scalar")
                    and _operand_ok(t, e.out.shape)
                    and len(_base(t).shape) >= 2):
                return "smul", [t, ins[i]]
    if op == "rmsnorm_bwd":
        if len(ins) != 3:
            return None
        x, w, g = ins
        if not (_operand_ok(x, e.out.shape)
                and _operand_ok(g, e.out.shape)
                and _operand_ok(w, e.out.shape)
                and len(_base(w).shape) == 1):
            return None
        return op, ins
    if op in ("softmax_bwd", "log_softmax_bwd"):
        if len(ins) != 2 or not all(
                _operand_ok(v, e.out.shape) and len(_base(v).shape) >= 2
                for v in ins):
            return None
        return op, ins
    if op in ("matmul", "matmul_t"):
        # operand trailing dims legitimately differ from the output's
        # (the contraction consumes them), so the row-operand gate below
        # does not apply; the matcher already enforced contraction legality
        if len(ins) != 2 or any(
                _base(v).kind == "const" or len(_base(v).shape) < 2
                for v in ins):
            return None
        return op, ins
    if not all(_operand_ok(v, e.out.shape) for v in ins):
        return None
    if op == "rmsnorm" and len(ins) == 1:
        # weightless form: single row operand, no learned gain
        if len(_base(ins[0]).shape) < 2:
            return None
        return op, ins
    if op in ("add", "mul", "sub", "swiglu", "rmsnorm"):
        if len(ins) != 2:
            return None
        r0, r1 = len(_base(ins[0]).shape), len(_base(ins[1]).shape)
        if r0 < 2 and r1 >= 2:
            if op in ("add", "mul"):     # commutative: row operand first
                ins = [ins[1], ins[0]]
            else:
                return None
        elif r0 < 2:
            return None
    elif op == "layernorm":
        if len(ins) != 3 or len(_base(ins[0]).shape) < 2:
            return None
    else:
        if len(ins) != 1 or len(_base(ins[0]).shape) < 2:
            return None
    return op, ins


def _prune_dead(eqns: List[_Eqn], outputs: List[_Val]) -> List[_Eqn]:
    """Keep only eqns (transitively) feeding the traced outputs."""
    prod = {_base(e.out).vid: e for e in eqns}
    live: Set[int] = set()
    stack = [_base(o).vid for o in outputs]
    while stack:
        vid = stack.pop()
        e = prod.get(vid)
        if e is None or id(e) in live:
            continue
        live.add(id(e))
        for v in e.ins:
            stack.append(_base(v).vid)
    return [e for e in eqns if id(e) in live]


# recipe-default eps per normalizing composite: a traced value that matches
# the default is elided from node attrs (keeps declared-fixture
# fingerprints byte-stable); anything else rides into the chain attrs
_EPS_DEFAULT = {"rmsnorm": 1e-6, "layernorm": 1e-5, "rmsnorm_bwd": 1e-6}


def _node_attrs(e: _Eqn, op: str) -> Tuple[Tuple[str, object], ...]:
    if op == "scale":
        return (("scale", float(e.params["scale"])),)
    eps = e.params.get("eps")
    default = _EPS_DEFAULT.get(op)
    if eps is None or default is None or _isclose(float(eps), default,
                                                 rel=1e-6):
        return ()
    return (("eps", float(eps)),)


def extract_graph(fn: Callable,
                  shapes: Sequence[Tuple[str, Tuple[int, ...]]],
                  *, name: str) -> OpGraph:
    """Trace ``fn`` on f32 examples of ``shapes`` (ordered ``(arg, shape)``
    pairs) and normalize the jaxpr into an :class:`OpGraph`."""
    import jax
    import jax.numpy as jnp

    shapes = [(str(n), tuple(int(s) for s in shp)) for n, shp in shapes]
    structs = [jax.ShapeDtypeStruct(shp, jnp.float32) for _, shp in shapes]
    try:
        closed = jax.make_jaxpr(fn)(*structs)
    except Exception as exc:  # noqa: BLE001 — tracing failure
        raise ExtractError(f"cannot trace workload '{name}': {exc}") from exc

    b = _Builder()
    args = [b.val(shp, "ext", name=arg) for arg, shp in shapes]
    outs = b.process_jaxpr(closed.jaxpr, list(closed.consts), args)
    # prune dead eqns BEFORE rewriting as well as after: VJP traces carry
    # dead forward-residual arithmetic whose uses of pattern-internal
    # values would otherwise defeat the composite matchers' only-used-
    # inside-the-pattern check
    eqns = _prune_dead(b.eqns, outs)
    rw = _Rewriter(eqns, outs)
    rw.run()
    eqns, outputs = rw.eqns, rw.outputs

    # ---- liveness: keep only eqns feeding the traced outputs -------------
    eqns = _prune_dead(eqns, outputs)

    # ---- naming ----------------------------------------------------------
    names: Dict[int, str] = {}
    for a in args:
        names[a.vid] = a.name
    t_idx = 0
    for e in eqns:
        for v in e.ins:
            bb = _base(v)
            if bb.kind == "ext" and bb.vid not in names:
                names[bb.vid] = bb.name          # synthesized masks
        t_idx += 1
        names[_base(e.out).vid] = f"%t{t_idx}"

    # ---- node emission ---------------------------------------------------
    nodes: List[OpNode] = []
    consumed: List[int] = []
    for e in eqns:
        fus = _fusable_eqn(e)
        if fus is not None:
            op, ins = fus
            attrs = _node_attrs(e, op)
        else:
            op = f"barrier.{e.prim}"
            ins = [v for v in e.ins if _base(v).kind != "const"]
            attrs = ()
        in_names = []
        for v in ins:
            bb = _base(v)
            in_names.append(names[bb.vid])
            consumed.append(bb.vid)
        nodes.append(OpNode(op, tuple(in_names), names[_base(e.out).vid],
                            out_rank=_crank(e.out.shape), attrs=attrs))

    ext_vals: Dict[int, _Val] = {}
    for a in args:
        ext_vals[a.vid] = a
    for e in eqns:
        for v in e.ins:
            bb = _base(v)
            if bb.kind == "ext":
                ext_vals.setdefault(bb.vid, bb)
    inputs = tuple((names[vid], _crank(ext_vals[vid].shape))
                   for vid, v in ext_vals.items() if vid in set(consumed))

    out_names = []
    produced = {n.output for n in nodes}
    for o in outputs:
        nm = names.get(_base(o).vid)
        if nm is not None and nm in produced and nm not in out_names:
            out_names.append(nm)
    if not out_names:
        raise ExtractError(f"workload '{name}' has no traced output "
                           f"produced by an extracted node")
    return OpGraph(name=name, inputs=inputs, outputs=tuple(out_names),
                   nodes=tuple(nodes))


# --------------------------------------------------------------------------
# Canonical renaming of proposed specs (name-stable fingerprinting)
# --------------------------------------------------------------------------

def canonicalize_spec(spec):
    """Rename synthesized tensors to the canonical vocabulary: the primary
    barrier-produced input becomes ``input``, synthesized mask inputs
    become ``mask``, links become ``h``/``h1..hk``, and the final stage's
    observed output becomes ``output``.  Traced argument names (which the
    workload library aligns with the golden fixtures) are kept."""
    taken = {t for t, _ in spec.inputs}
    ren: Dict[str, str] = {}

    def fresh(base: str) -> str:
        cand, k = base, 1
        while cand in taken or cand in ren.values():
            k += 1
            cand = f"{base}{k}"
        return cand

    for idx, (t, _r) in enumerate(spec.inputs):
        if not t.startswith("%"):
            continue
        if t.startswith("%mask"):
            ren[t] = fresh("mask")
        elif idx == 0:
            ren[t] = fresh("input")
        else:
            ren[t] = fresh(f"x{idx}")
    links = [st.output for st in spec.stages]
    last = links[-1] if links else None
    if last is not None and last in spec.outputs and last.startswith("%"):
        ren[last] = fresh("output")
    todo = [t for t in links if t.startswith("%") and t not in ren]
    if len(todo) == 1:
        ren[todo[0]] = fresh("h")
    else:
        for k, t in enumerate(todo):
            ren[t] = fresh(f"h{k + 1}")

    def r(t):
        return ren.get(t, t)

    def rk(k):
        # per-stage qualified attr keys ('scale@%t3') carry tensor names
        if "@" in k:
            base_k, t = k.split("@", 1)
            return f"{base_k}@{r(t)}"
        return k

    from .chain import ChainSpec, ChainStage   # late: avoids import cycle
    return ChainSpec(
        name=spec.name,
        inputs=tuple((r(t), rank) for t, rank in spec.inputs),
        outputs=tuple(r(t) for t in spec.outputs),
        stages=tuple(ChainStage(st.op, tuple(r(t) for t in st.inputs),
                                r(st.output)) for st in spec.stages),
        keep=tuple((r(a), r(b)) for a, b in spec.keep),
        route=tuple((r(a), r(b)) for a, b in spec.route),
        pad_values=tuple((r(t), v) for t, v in spec.pad_values),
        attrs=tuple(sorted((rk(k), v) for k, v in spec.attrs)))


def extract_chains(fn: Callable,
                   shapes: Sequence[Tuple[str, Tuple[int, ...]]],
                   *, name: str):
    """Trace → normalize → propose → canonicalize: the full extraction
    pipeline for one workload function."""
    graph = extract_graph(fn, shapes, name=name)
    return [canonicalize_spec(s) for s in propose_chains(graph)]


def extracted_chains():
    """Extraction over the model workload library: the authoritative chain
    source (``chain.py`` fingerprint-dedupes it against the declared golden
    fixtures).  Returns ``[(spec, workload_name), ...]`` in deterministic
    workload order."""
    from ...models.workloads import WORKLOADS
    out = []
    for w in WORKLOADS:
        for spec in extract_chains(w.fn, w.shapes, name=w.name):
            out.append((spec, w.name))
    return out
