"""Expert example — ELEMENTWISE pattern.

Category coverage: activation, pointwise math, optimizer updates and the
pointwise half of losses.  Strategy (the category-level knowledge the paper
encodes in its expert examples):

  * flatten all tensors; partition contiguous spans across cores,
  * tile each span so one tile per live tensor fits the UB/VMEM budget,
  * the GM layout is padded on the trailing axis to a full core*tile span
    (Pass 4), so every transfer is full-size and lane-aligned — this is what
    makes the kernel eligible for the BlockSpec-pipelined backend (double
    buffering comes from the Pallas pipeline, as queue capacity 2 does on
    Ascend).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from ..dsl import ast as A
from ..dsl import language as tl
from ..lowering.pipeline import Knobs
from .common import RecipeCtx, Recipe, two_phase_build

# elements in one (8 sublane, 128 lane) tile of 32-bit values
FLAT_TILE = 8 * 128


def build_elementwise(task, shapes: Dict[str, Tuple[int, ...]], knobs: Knobs,
                      recipe: Recipe) -> A.Program:
    layout = {
        t.name: {"flatten": True, "pad_multiple": "core_span",
                 "pad_value": float(task.attrs.get("pad_value", 0.0))}
        for t in task.tensors
    }

    def core(shp):
        return _build_elementwise_core(task, shp, knobs, recipe)

    prog = two_phase_build(core, shapes, layout)
    prog.meta["out_shape_code"] = {
        t.name: "tuple(_arrs[0].shape)" for t in task.tensors
        if t.role == "out"
    }
    return prog


def _build_elementwise_core(task, shapes: Dict[str, Tuple[int, ...]],
                            knobs: Knobs, recipe: Recipe) -> A.Program:
    ins = [t for t in task.tensors if t.role in ("in", "inout")]
    outs = [t for t in task.tensors if t.role in ("out", "inout")]
    first = ins[0].name

    P = tl.ProgramBuilder(task.name, category=task.category,
                          task_shapes=dict(shapes),
                          rationale="elementwise: flat span partition, "
                                    "pipelined tiles")
    h = P.host()
    numel = h.numel(first)
    n_cores = h.let("n_cores", tl.NUM_CORES,
                    rationale="fixed vector-core count")
    # a rank-1 block must be a whole number of the chip's (8, 128) f32
    # tiles, or Mosaic refuses it: round the per-core share up to that
    per_core_share = tl.hcdiv(tl.hcdiv(numel, n_cores), FLAT_TILE) * FLAT_TILE
    tile_length = h.let(
        "tile_length", tl.hmin(knobs.max_tile, per_core_share),
        rationale=f"tile so {len(task.tensors)} live tiles fit the UB/VMEM "
                  f"budget; lane-aligned by Pass-4 padding")
    core_span = h.let("core_span", n_cores * tile_length,
                      rationale="GM padded to a multiple of this (pass 4)")
    padded_numel = h.let("padded_numel",
                         tl.hcdiv(numel, core_span) * core_span)
    per_core = h.let("per_core", padded_numel // n_cores)
    n_tiles = h.let("n_tiles", per_core // tile_length)
    h.launch(grid="n_cores")

    dts = {t.name: t.dtype for t in task.tensors}
    with P.kernel(tensors=[(t.name, t.dtype, t.role, t.rank)
                           for t in task.tensors]):
        pid = tl.program_id(0)
        bufs = {t.name: tl.alloc_ub(f"{t.name}_t", (tile_length,), t.dtype)
                for t in ins}
        ctx = RecipeCtx(pb=P, attrs=dict(task.attrs), bufs=bufs,
                        tile_shape=(tile_length,),
                        dtype=dts[outs[0].name])
        with tl.for_range("t", 0, n_tiles) as t:
            off = pid * per_core + t * tile_length
            with tl.copyin():
                for tp in ins:
                    tl.load(tp.name, off, bufs[tp.name])
            with tl.compute():
                ctx.extras["off"] = off
                recipe(ctx)
            with tl.copyout():
                for tp in outs:
                    tl.store(tp.name, off, ctx.result(tp.name))

    return P.build()


# --------------------------------------------------------------------------
# Recipes: activations & pointwise math
# --------------------------------------------------------------------------

_SIMPLE_UNARY = (
    "relu", "sigmoid", "tanh", "gelu", "silu", "softplus", "elu", "selu",
    "hardsigmoid", "hardswish", "mish", "softsign", "exp", "log", "sqrt",
    "rsqrt", "abs", "neg", "erf", "square", "reciprocal", "log1p", "expm1",
    "sign", "floor",
)


def unary_recipe(opname: str) -> Recipe:
    def recipe(ctx: RecipeCtx):
        x = ctx.buf(ctx.attrs["input"])
        y = ctx.tmp("y")
        getattr(tl, opname)(y, x)
        ctx.out(ctx.attrs["output"], y)
    recipe.__name__ = f"recipe_{opname}"
    return recipe


def leaky_relu_recipe(ctx: RecipeCtx):
    x = ctx.buf(ctx.attrs["input"])
    alpha = float(ctx.attrs.get("alpha", 0.01))
    y, m, t = ctx.tmp("y"), ctx.tmp("m"), ctx.tmp("t")
    tl.gt(m, x, 0.0)
    tl.mul(t, x, alpha)
    tl.where(y, m, x, t)
    ctx.out(ctx.attrs["output"], y)


def relu6_recipe(ctx: RecipeCtx):
    x = ctx.buf(ctx.attrs["input"])
    y = ctx.tmp("y")
    tl.clamp(y, x, 0.0, 6.0)
    ctx.out(ctx.attrs["output"], y)


def hardtanh_recipe(ctx: RecipeCtx):
    x = ctx.buf(ctx.attrs["input"])
    y = ctx.tmp("y")
    tl.clamp(y, x, float(ctx.attrs.get("min_val", -1.0)),
             float(ctx.attrs.get("max_val", 1.0)))
    ctx.out(ctx.attrs["output"], y)


# --------------------------------------------------------------------------
# Recipes: optimizers (multi-tensor elementwise, INOUT states)
# --------------------------------------------------------------------------

def sgd_recipe(ctx: RecipeCtx):
    p, g = ctx.buf("param"), ctx.buf("grad")
    lr = float(ctx.attrs["lr"])
    t = ctx.tmp("t")
    np_ = ctx.tmp("new_p")
    tl.mul(t, g, lr)
    tl.sub(np_, p, t)
    ctx.out("param", np_)


def sgd_momentum_recipe(ctx: RecipeCtx):
    p, g, m = ctx.buf("param"), ctx.buf("grad"), ctx.buf("mom")
    lr, mu = float(ctx.attrs["lr"]), float(ctx.attrs["momentum"])
    mm, t, np_ = ctx.tmp("new_m"), ctx.tmp("t"), ctx.tmp("new_p")
    tl.mul(mm, m, mu)
    tl.add(mm, mm, g)
    tl.mul(t, mm, lr)
    tl.sub(np_, p, t)
    ctx.out("param", np_)
    ctx.out("mom", mm)


def _adam_core(ctx: RecipeCtx, weight_decay: float):
    p, g = ctx.buf("param"), ctx.buf("grad")
    m, v = ctx.buf("m"), ctx.buf("v")
    a = ctx.attrs
    lr, b1, b2, eps = (float(a["lr"]), float(a["beta1"]), float(a["beta2"]),
                       float(a["eps"]))
    step = int(a["step"])
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step
    nm, nv, t, u, np_ = (ctx.tmp("new_m"), ctx.tmp("new_v"), ctx.tmp("t"),
                         ctx.tmp("u"), ctx.tmp("new_p"))
    tl.mul(nm, m, b1)
    tl.mul(t, g, 1.0 - b1)
    tl.add(nm, nm, t)
    tl.mul(nv, v, b2)
    tl.square(t, g)
    tl.mul(t, t, 1.0 - b2)
    tl.add(nv, nv, t)
    # update = lr * (m/bc1) / (sqrt(v/bc2) + eps)
    tl.mul(t, nv, 1.0 / bc2)
    tl.sqrt(t, t)
    tl.add(t, t, eps)
    tl.mul(u, nm, lr / bc1)
    tl.div(u, u, t)
    if weight_decay:
        wd = ctx.tmp("wd")
        tl.mul(wd, p, lr * weight_decay)
        tl.add(u, u, wd)
    tl.sub(np_, p, u)
    ctx.out("param", np_)
    ctx.out("m", nm)
    ctx.out("v", nv)


def adam_recipe(ctx: RecipeCtx):
    _adam_core(ctx, 0.0)


def adamw_recipe(ctx: RecipeCtx):
    _adam_core(ctx, float(ctx.attrs.get("weight_decay", 0.01)))


def adagrad_recipe(ctx: RecipeCtx):
    p, g, acc = ctx.buf("param"), ctx.buf("grad"), ctx.buf("acc")
    lr, eps = float(ctx.attrs["lr"]), float(ctx.attrs.get("eps", 1e-10))
    na, t, np_ = ctx.tmp("new_acc"), ctx.tmp("t"), ctx.tmp("new_p")
    tl.square(t, g)
    tl.add(na, acc, t)
    tl.sqrt(t, na)
    tl.add(t, t, eps)
    tl.div(t, g, t)
    tl.mul(t, t, lr)
    tl.sub(np_, p, t)
    ctx.out("param", np_)
    ctx.out("acc", na)


def rmsprop_recipe(ctx: RecipeCtx):
    p, g, s = ctx.buf("param"), ctx.buf("grad"), ctx.buf("sq")
    a = ctx.attrs
    lr, rho, eps = float(a["lr"]), float(a["rho"]), float(a.get("eps", 1e-8))
    ns, t, np_ = ctx.tmp("new_s"), ctx.tmp("t"), ctx.tmp("new_p")
    tl.mul(ns, s, rho)
    tl.square(t, g)
    tl.mul(t, t, 1.0 - rho)
    tl.add(ns, ns, t)
    tl.sqrt(t, ns)
    tl.add(t, t, eps)
    tl.div(t, g, t)
    tl.mul(t, t, lr)
    tl.sub(np_, p, t)
    ctx.out("param", np_)
    ctx.out("sq", ns)
