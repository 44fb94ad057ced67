"""Transcompiler end-to-end: both backends, oracle equivalence, feedback."""
import numpy as np
import pytest

from repro.core.dsl import ast as A
from repro.core.dsl import language as tl
from repro.core.dsl.interp import interpret
from repro.core.lowering import transcompile, generate_with_feedback, Knobs
from repro.core.lowering.pipeline import TranscompileError


def build_elementwise_chain(shapes, ops, pad=False):
    """Simple flat elementwise chain used across these tests."""
    from repro.core.examples.common import two_phase_build

    def core(shp):
        P = tl.ProgramBuilder("chain", category="test", task_shapes=shp)
        h = P.host()
        numel = h.numel("input")
        n_cores = h.let("n_cores", 8)
        tile = h.let("tile_length", tl.hmin(512, tl.hcdiv(numel, n_cores)))
        span = h.let("core_span", n_cores * tile)
        pn = h.let("padded_numel", tl.hcdiv(numel, span) * span)
        per_core = h.let("per_core", pn // n_cores)
        n_tiles = h.let("n_tiles", per_core // tile)
        h.launch(grid="n_cores")
        with P.kernel(tensors=[("input", tl.f32, "in", 1),
                               ("output", tl.f32, "out", 1)]):
            pid = tl.program_id(0)
            buf = tl.alloc_ub("buf", (tile,), tl.f32)
            with tl.for_range("t", 0, n_tiles) as t:
                off = pid * per_core + t * tile
                with tl.copyin():
                    tl.load("input", off, buf)
                with tl.compute():
                    for op in ops:
                        (getattr(tl, op) if isinstance(op, str) else op)(
                            buf, buf)
                with tl.copyout():
                    tl.store("output", off, buf)
        return P.build()

    layout = {
        "input": {"flatten": True, "pad_multiple": "core_span",
                  "pad_value": 0.0},
        "output": {"flatten": True, "pad_multiple": "core_span",
                   "pad_value": 0.0},
    }
    return two_phase_build(core, shapes, layout)


def _np_chain(x, ops):
    fns = {"tanh": np.tanh, "exp": np.exp, "sigmoid":
           lambda v: 1 / (1 + np.exp(-v)), "square": lambda v: v * v,
           "abs": np.abs, "neg": lambda v: -v,
           "softsign": lambda v: v / (1 + np.abs(v))}
    y = x.astype(np.float64)
    for op in ops:
        y = fns[op](y)
    return y


@pytest.mark.parametrize("numel", [4096, 5000, 131])
def test_elementwise_chain_both_paths(numel):
    shapes = {"input": (numel,), "output": (numel,)}
    ops = ["tanh", "square", "softsign"]
    prog = build_elementwise_chain(shapes, ops)
    art = transcompile(prog)
    assert art.backend == "pipelined"
    fn = art.module.make(shapes, interpret=True)
    x = np.random.RandomState(0).randn(numel).astype(np.float32)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, _np_chain(x, ops), rtol=1e-5, atol=1e-6)

    # explicit backend must agree with pipelined
    art2 = transcompile(prog, force_backend="explicit")
    out2 = np.asarray(art2.module.make(shapes, interpret=True)(x))
    np.testing.assert_allclose(out2, out, rtol=1e-6, atol=1e-7)


def test_lowered_matches_interpreter_oracle():
    numel = 2048
    shapes = {"input": (numel,), "output": (numel,)}
    prog = build_elementwise_chain(shapes, ["sigmoid", "neg"])
    art = transcompile(prog)
    x = np.random.RandomState(1).randn(numel).astype(np.float32)
    # interp runs on the PADDED task shapes the program was built with
    pshapes = prog.meta["task_shapes"]
    want = interpret(prog, {"input": x.reshape(pshapes["input"])},
                     {"output": pshapes["output"]})["output"]
    got = np.asarray(art.module.make(shapes, interpret=True)(x))
    np.testing.assert_allclose(got.reshape(-1), want.reshape(-1)[:numel],
                               rtol=1e-5, atol=1e-6)


def test_iota_is_int32_before_its_cast():
    """Mosaic lowers integer iotas only: an f32 index tile is emitted as an
    int32 iota cast to the buffer dtype, and still counts 0..tile-1."""
    numel = 2048
    shapes = {"input": (numel,), "output": (numel,)}
    prog = build_elementwise_chain(
        shapes, [lambda dst, src: tl.iota(dst, axis=0)])
    art = transcompile(prog)
    iotas = [ln for ln in art.source.splitlines() if "broadcasted_iota" in ln]
    assert iotas and all(
        "broadcasted_iota(jnp.int32" in ln and ".astype(jnp.float32)" in ln
        for ln in iotas), iotas
    tile = prog.meta["plan"]["tile_length"]
    out = np.asarray(art.module.make(shapes, interpret=True)(
        np.zeros(numel, np.float32)))
    np.testing.assert_array_equal(out, np.arange(numel) % tile)


def test_generated_source_is_readable_artifact():
    shapes = {"input": (1024,), "output": (1024,)}
    prog = build_elementwise_chain(shapes, ["exp"])
    art = transcompile(prog)
    src = art.source
    # the properties RQ3 relies on: header, host plan, staged structure
    assert "pl.pallas_call" in src
    assert "pl.BlockSpec" in src
    assert "def _plan(" in src
    assert "copyin" in src and "copyout" in src
    assert "rationale" in src or "#" in src
    compile(src, "<artifact>", "exec")   # syntactically valid standalone


def test_feedback_loop_budget_shrinks_tile():
    """A builder that over-allocates VMEM on the first attempt must be
    repaired by the tile-shrinking feedback (paper per-pass correction)."""
    calls = []

    def builder(knobs: Knobs):
        calls.append(knobs.max_tile)
        shapes = {"input": (1 << 14,), "output": (1 << 14,)}
        P = tl.ProgramBuilder("big", task_shapes=shapes)
        h = P.host()
        h.let("n_cores", 1)
        tile = h.let("tile_length", min(knobs.max_tile, 1 << 14))
        h.launch(grid="n_cores")
        with P.kernel(tensors=[("input", tl.f32, "in", 1),
                               ("output", tl.f32, "out", 1)]):
            # allocate WAY too many buffers at the requested tile
            bufs = [tl.alloc_ub(f"b{i}", (tile,), tl.f32)
                    for i in range(600)]
            with tl.copyin():
                tl.load("input", 0, bufs[0])
            with tl.compute():
                tl.copy(bufs[1], bufs[0])
            with tl.copyout():
                tl.store("output", 0, bufs[1])
        return P.build()

    art = generate_with_feedback(builder, Knobs(max_tile=16384))
    assert len(calls) > 1 and calls[-1] < calls[0]
    assert any("feedback" in line for line in art.pass_log)


def test_tque_tbuf_classification_logged():
    shapes = {"input": (1024,), "output": (1024,)}
    prog = build_elementwise_chain(shapes, ["tanh"])
    art = transcompile(prog)
    log = "\n".join(art.pass_log)
    assert "TQue(in)" in log and "TBuf" in log
