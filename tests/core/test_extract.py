"""Jaxpr-level graph extraction (DESIGN.md §11): golden re-derivation of
every declared chain from traced model code, composite recognition,
barrier segmentation (dot_general / scan / dynamic_slice), masked-fill
canonicalization, barrier-cycle legality, naming/fingerprint stability and
determinism."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.fusion import (CHAINS, CHAIN_SOURCES, GRAPHS, OpGraph,
                               OpNode, ProposeError, chain_fingerprint,
                               extract_chains, extract_graph,
                               extracted_chains, propose_chains)
from repro.models.workloads import WORKLOADS

W = {w.name: w for w in WORKLOADS}


# ---------------------------------------------------------------------------
# Golden: extraction re-derives every declared fixture chain byte-identically
# ---------------------------------------------------------------------------

def test_extraction_rederives_all_declared_chains_byte_identical():
    """Every chain proposable from the hand-declared GRAPHS fixtures must
    also be derived by tracing the model workload library — and the
    registered CHAINS entry must be the fixture spec verbatim (stages,
    keep/route, pad values, tensor names), so planner registry entries,
    cache keys and kernels/generated/ artifacts cannot churn."""
    declared = {}
    for g in GRAPHS:
        for spec in propose_chains(g):
            declared[spec.name] = spec
    assert len(declared) == 6
    extracted_fps = {chain_fingerprint(s) for s, _ in extracted_chains()}
    for name, spec in declared.items():
        assert chain_fingerprint(spec) in extracted_fps, (
            f"extraction lost declared chain '{name}'")
        assert CHAINS[name] == spec, (
            f"registered '{name}' is not the declared fixture spec")
        assert CHAIN_SOURCES[name] == ("declared", "extracted")


def test_add_rmsnorm_extracted_from_real_ffn_block():
    """The add_rmsnorm chain comes out of the REAL pre-FFN segment
    (residual update + apply_norm flanked by the FFN matmuls), with the
    matmul barriers visible in the extracted graph and the escaping
    residual stream kept."""
    w = W["add_rmsnorm"]
    graph = extract_graph(w.fn, w.shapes, name=w.name)
    assert sum(n.op == "barrier.dot_general" for n in graph.nodes) == 3
    (spec,) = propose_chains(graph)
    assert [st.op for st in spec.stages] == ["add", "rmsnorm"]
    assert len(spec.keep) == 1                 # residual stream escapes
    declared = CHAINS["add_rmsnorm"]
    assert chain_fingerprint(spec) == chain_fingerprint(declared)


def test_barrier_cycle_does_not_swallow_post_ffn_residual_add():
    """The FFN output is added back onto the residual stream the chain
    itself produced: merging that add into the chain would make the fused
    kernel consume a tensor that only exists after it has run.  The
    proposer must stop the chain at {add, rmsnorm} — exactly one chain,
    two stages — instead of emitting a 3-stage unschedulable one."""
    w = W["add_rmsnorm"]
    specs = extract_chains(w.fn, w.shapes, name=w.name)
    assert len(specs) == 1
    assert len(specs[0].stages) == 2


# ---------------------------------------------------------------------------
# The NEW extracted chain: flash_attention THROUGH the matmul barriers
# ---------------------------------------------------------------------------

def test_flash_attention_extracted_through_matmul_barriers():
    """Tracing the real mha_reference yields ONE chain spanning both
    contractions: the qk^T and pv dot_generals classify as matmul stages
    (not barriers), where(causal, logits, -inf) is canonicalized into
    add(input, mask) and the softmax pattern collapses — the full
    flash-attention recipe derived from unmodified model code."""
    w = W["flash_attention"]
    graph = extract_graph(w.fn, w.shapes, name=w.name)
    ops = [n.op for n in graph.nodes]
    assert "barrier.dot_general" not in ops      # matmuls are now stages
    assert ops == ["matmul_t", "scale", "add", "softmax", "matmul"]
    assert "barrier.select_n" not in ops         # masked fill rewritten
    (spec,) = propose_chains(graph)
    assert [st.op for st in spec.stages] == [
        "matmul_t", "scale", "add", "softmax", "matmul"]
    # the traced qk scale (1/sqrt(head_dim)) rides the chain attrs
    assert abs(dict(spec.attrs)["scale"] - 0.25) < 1e-12


def test_flash_attention_registered_chain_structure():
    spec = CHAINS["flash_attention"]
    assert CHAIN_SOURCES["flash_attention"] == ("extracted",)
    assert spec.inputs == (("q", 2), ("k", 2), ("mask", 2), ("v", 2))
    assert spec.outputs == ("output",)
    assert [(st.op, st.inputs, st.output) for st in spec.stages] == [
        ("matmul_t", ("q", "k"), "h1"),
        ("scale", ("h1",), "h2"),
        ("add", ("h2", "mask"), "h3"),
        ("softmax", ("h3",), "h4"),
        ("matmul", ("h4", "v"), "output")]
    pads = dict(spec.pad_values)
    assert pads["mask"] == -3.0e38               # padded keys stay masked
    assert pads["h4"] == 0.0                     # padded probs contribute 0
    # q/k/v carry no explicit pad: the default zero-pad is matmul-neutral
    assert not {"q", "k", "v"} & set(pads)


def test_mask_softmax_registered_chain_structure():
    spec = CHAINS["mask_softmax"]
    assert CHAIN_SOURCES["mask_softmax"] == ("extracted",)
    assert spec.inputs == (("input", 2), ("mask", 2))
    assert spec.outputs == ("output",)
    assert [(st.op, st.inputs, st.output) for st in spec.stages] == [
        ("add", ("input", "mask"), "h"),
        ("softmax", ("h",), "output")]
    # neutral pad propagated backward through the mask add
    assert dict(spec.pad_values) == {"input": -3.0e38}


def test_mask_softmax_registered_end_to_end():
    """The extracted chain rides the full pipeline: planner default +
    streaming fallback, tuner variant, fused-suite task with the chain
    fingerprint in its cache attrs, checked-in generated artifact."""
    from repro.bench.tasks import fused_suite
    from repro.core.planner import PLANNER_REGISTRY
    from repro.core.tuning import variants_for
    assert "mask_softmax" in PLANNER_REGISTRY
    assert "mask_softmax_streaming" in PLANNER_REGISTRY
    assert "fused" in variants_for("mask_softmax")
    task = {t.name: t for t in fused_suite()}["mask_softmax"]
    assert task.attrs["chain_fingerprint"] == \
        chain_fingerprint(CHAINS["mask_softmax"])
    import repro.kernels.generated.mask_softmax as art
    assert callable(art.make)


def test_full_transformer_block_chains_all_dedupe():
    """The full pre-norm transformer layer is the end-to-end validation
    workload: everything fusable it contains must fingerprint-dedupe onto
    already-registered chains (the full flash_attention chain from the
    attention path — its scores segment no longer stops at the matmul
    barriers — and add_rmsnorm from the pre-FFN segment) — no accidental
    near-duplicate registrations."""
    w = W["transformer_block"]
    specs = extract_chains(w.fn, w.shapes, name=w.name)
    fps = sorted(chain_fingerprint(s) for s in specs)
    assert fps == sorted((chain_fingerprint(CHAINS["flash_attention"]),
                          chain_fingerprint(CHAINS["add_rmsnorm"])))


# ---------------------------------------------------------------------------
# Composite recognition units
# ---------------------------------------------------------------------------

def _single_chain(fn, shapes, name="unit"):
    specs = extract_chains(fn, shapes, name=name)
    assert len(specs) == 1, [s.name for s in specs]
    return specs[0]


@pytest.mark.parametrize("fn,ops", [
    (lambda x, b: jax.nn.gelu(x + b, approximate=True), ["add", "gelu"]),
    (lambda x, b: jax.nn.gelu(x + b, approximate=False), ["add", "gelu"]),
    (lambda x, b: jax.nn.silu(x + b), ["add", "silu"]),
    (lambda x, b: (lambda h: h * jax.nn.sigmoid(h))(x + b),
     ["add", "silu"]),
    (lambda x, b: jax.nn.relu(x + b), ["add", "relu"]),
    (lambda x, b: jnp.square(x + b), ["add", "square"]),
    (lambda x, b: jnp.tanh(x * b), ["mul", "tanh"]),
    (lambda x, b: jax.nn.silu(x + b) * x, ["add", "swiglu"]),
])
def test_composite_recognition(fn, ops):
    spec = _single_chain(fn, (("input", (4, 64)), ("bias", (64,))))
    assert [st.op for st in spec.stages] == ops


def _jaxpr_prims(fn, *shapes):
    closed = jax.make_jaxpr(fn)(*[jnp.zeros(s, jnp.float32) for s in shapes])
    return [e.primitive.name for e in closed.jaxpr.eqns]


@pytest.mark.parametrize("fn,ops", [
    (lambda x, b: jax.nn.silu(x + b), ["add", "silu"]),
    (lambda x, b: jax.jit(jax.nn.softmax)(x + b), ["add", "softmax"]),
])
def test_jit_wrapped_composites_are_inlined(fn, ops):
    """``jax.nn.silu`` arrives wrapped in a ``jit`` call primitive, as does
    any jitted helper; extraction must look through it, not keep it as an
    opaque barrier."""
    assert "jit" in _jaxpr_prims(fn, (4, 64), (64,))
    spec = _single_chain(fn, (("input", (4, 64)), ("bias", (64,))))
    assert [st.op for st in spec.stages] == ops


def test_literals_extract_as_constants():
    """``max(h, 0.0)`` carries its 0.0 as a jaxpr Literal: read as a
    constant it completes the relu composite."""
    from jax.extend.core import Literal
    fn = lambda x, b: jnp.maximum(x + b, 0.0)  # noqa: E731
    closed = jax.make_jaxpr(fn)(jnp.zeros((4, 64)), jnp.zeros(64))
    assert any(isinstance(v, Literal)
               for e in closed.jaxpr.eqns for v in e.invars)
    spec = _single_chain(fn, (("input", (4, 64)), ("bias", (64,))))
    assert [st.op for st in spec.stages] == ["add", "relu"]


def test_rank3_model_tensors_canonicalize_to_rank2_chains():
    """(B, S, d) activations flatten to row tensors; trailing-broadcast
    weights stay rank-1 vectors."""
    from repro.models import layers as L
    from repro.models.workloads import _CFG
    spec = _single_chain(
        lambda x, w: jax.nn.silu(L.apply_norm({"scale": w}, x, _CFG)),
        (("input", (2, 8, 64)), ("weight", (64,))))
    assert spec.inputs == (("input", 2), ("weight", 1))
    assert [st.op for st in spec.stages] == ["rmsnorm", "silu"]


# ---------------------------------------------------------------------------
# Barrier segmentation: unsupported primitives segment, never mis-fuse
# ---------------------------------------------------------------------------

def test_dot_general_barrier_segments_extracted_graph():
    def fn(x, b, w, v):
        h = jax.nn.gelu(x + b)
        m = h @ w                       # matmul barrier
        return jnp.tanh(m * v)

    shapes = (("x", (8, 64)), ("b", (64,)), ("w", (64, 64)), ("v", (64,)))
    graph = extract_graph(fn, shapes, name="seg")
    assert any(n.op == "barrier.dot_general" for n in graph.nodes)
    first, second = propose_chains(graph)
    assert [st.op for st in first.stages] == ["add", "gelu"]
    assert [st.op for st in second.stages] == ["mul", "tanh"]
    # the matmul's output re-enters the downstream chain as a plain input
    barrier_out = next(n.output for n in graph.nodes
                       if n.op == "barrier.dot_general")
    assert second.inputs[0] == (barrier_out, 2)


def test_scan_barrier_segments_extracted_graph():
    def fn(x, b, v):
        h = jax.nn.silu(x + b)
        _, ys = jax.lax.scan(lambda c, row: (c + row, c + row),
                             jnp.zeros(x.shape[1]), h)
        return jnp.exp(ys * v)

    shapes = (("x", (8, 64)), ("b", (64,)), ("v", (64,)))
    graph = extract_graph(fn, shapes, name="seg_scan")
    assert any(n.op == "barrier.scan" for n in graph.nodes)
    specs = propose_chains(graph)
    assert [[st.op for st in s.stages] for s in specs] == [
        ["add", "silu"], ["mul", "exp"]]


def test_dynamic_slice_barrier_segments_extracted_graph():
    def fn(x, b, v):
        h = jax.nn.gelu(x + b)
        s = jax.lax.dynamic_slice(h, (0, 0), (4, x.shape[1]))
        return jnp.tanh(s * v)

    shapes = (("x", (8, 64)), ("b", (64,)), ("v", (64,)))
    graph = extract_graph(fn, shapes, name="seg_ds")
    assert any(n.op == "barrier.dynamic_slice" for n in graph.nodes)
    specs = propose_chains(graph)
    assert [[st.op for st in s.stages] for s in specs] == [
        ["add", "gelu"], ["mul", "tanh"]]


def test_barrier_nodes_carry_true_out_rank():
    """A reduction barrier's output is rank-1 — OpNode.out_rank must say
    so (inferring from the input would claim rank 2 and corrupt any
    downstream chain's primary-input rank check)."""
    graph = extract_graph(lambda x: jnp.sum(x, axis=-1) * 1.0,
                          (("x", (8, 64)),), name="red")
    red = next(n for n in graph.nodes if n.op == "barrier.reduce_sum")
    assert red.out_rank == 1


def test_pad_unsound_extraction_refuses_with_propose_error():
    """sigmoid -> softmax: no pad value survives sigmoid into softmax's
    neutral element, so the proposer must refuse the extracted chain
    rather than mis-fuse (same rule as declared graphs)."""
    with pytest.raises(ProposeError):
        extract_chains(lambda x: jax.nn.softmax(jax.nn.sigmoid(x), axis=-1),
                       (("x", (4, 64)),), name="bad")


# ---------------------------------------------------------------------------
# Masked-fill canonicalization gating
# ---------------------------------------------------------------------------

def test_masked_fill_only_rewrites_into_softmax():
    """where(pred, x, -inf) NOT consumed by a softmax keeps its select_n
    barrier — the additive-mask rewrite is only neutral under a softmax
    consumer."""
    def fn(x, m, b):
        return jnp.tanh(jnp.where(m > 0.0, x, -jnp.inf) + b)

    shapes = (("x", (4, 64)), ("m", (4, 64)), ("b", (64,)))
    graph = extract_graph(fn, shapes, name="nomask")
    assert any(n.op == "barrier.select_n" for n in graph.nodes)
    assert not any(t.startswith("%mask") for t, _ in graph.inputs)


def test_masked_fill_rewrite_synthesizes_mask_input():
    def fn(x, m):
        return jax.nn.softmax(jnp.where(m > 0.0, x, -jnp.inf), axis=-1)

    shapes = (("x", (4, 64)), ("m", (4, 64)))
    spec = _single_chain(fn, shapes, name="masked")
    assert [st.op for st in spec.stages] == ["add", "softmax"]
    assert ("mask", 2) in spec.inputs
    assert chain_fingerprint(spec) == \
        chain_fingerprint(CHAINS["mask_softmax"])


# ---------------------------------------------------------------------------
# Determinism and naming stability
# ---------------------------------------------------------------------------

def test_extraction_is_deterministic_across_runs():
    """Two full extraction sweeps produce identical specs in identical
    order — the precondition for the CI byte-determinism gate (which
    additionally re-runs extraction under two PYTHONHASHSEEDs)."""
    a = extracted_chains()
    b = extracted_chains()
    assert [(s.name, chain_fingerprint(s), s) for s, _ in a] == \
           [(s.name, chain_fingerprint(s), s) for s, _ in b]


def test_canonical_naming_is_stable_for_new_chains():
    """Chains with no declared fixture get deterministic canonical names:
    primary barrier-produced input -> 'input', synthesized mask -> 'mask',
    single link -> 'h', final observed output -> 'output'."""
    w = W["mask_softmax"]
    (spec,) = extract_chains(w.fn, w.shapes, name=w.name)
    assert spec.inputs == (("input", 2), ("mask", 2))
    assert spec.stages[0].output == "h"
    assert spec.outputs == ("output",)


def test_fingerprint_is_alpha_invariant_and_structure_sensitive():
    from repro.core.fusion import ChainSpec, ChainStage
    a = ChainSpec(name="a", inputs=(("x", 2), ("s", 1)),
                  outputs=("y",),
                  stages=(ChainStage("mul", ("x", "s"), "t"),
                          ChainStage("softmax", ("t",), "y")),
                  pad_values=(("x", -3.0e38), ("s", 1.0)))
    b = ChainSpec(name="b", inputs=(("input", 2), ("scale", 1)),
                  outputs=("output",),
                  stages=(ChainStage("mul", ("input", "scale"), "h"),
                          ChainStage("softmax", ("h",), "output")),
                  pad_values=(("input", -3.0e38), ("scale", 1.0)))
    assert chain_fingerprint(a) == chain_fingerprint(b)
    assert chain_fingerprint(a) == chain_fingerprint(CHAINS["mul_softmax"])
    c = ChainSpec(name="c", inputs=(("x", 2), ("s", 1)),
                  outputs=("y",),
                  stages=(ChainStage("add", ("x", "s"), "t"),
                          ChainStage("softmax", ("t",), "y")),
                  pad_values=(("x", -3.0e38),))
    assert chain_fingerprint(c) != chain_fingerprint(a)


# ---------------------------------------------------------------------------
# Non-default norm eps (DESIGN.md §12 satellite): the traced eps rides the
# composite's params into the chain attrs instead of hard-pinning 1e-6
# ---------------------------------------------------------------------------

def test_non_default_rmsnorm_eps_is_carried_not_barriered():
    """apply_norm with a non-default eps used to silently BARRIER the
    rmsnorm composite (the matcher hard-pinned eps == 1e-6).  Now any
    small eps matches and the traced value lands in the chain's attrs, so
    the recipe computes with the model's eps."""
    from repro.models import layers as L
    from repro.models.workloads import _CFG
    specs = extract_chains(
        lambda x, w: jax.nn.silu(L.apply_norm({"scale": w}, x, _CFG,
                                              eps=1e-5)),
        (("input", (4, 64)), ("weight", (64,))), name="eps_chain")
    assert len(specs) == 1
    assert [st.op for st in specs[0].stages] == ["rmsnorm", "silu"]
    eps = dict(specs[0].attrs)["eps"]
    assert abs(eps - 1e-5) < 1e-9

    # and the built chain USES it: differential vs the eps-aware oracle
    from repro.core.fusion import build_chain
    from repro.core.dsl.interp import interpret
    rows, cols = 4, 96
    shapes = {"input": (rows, cols), "weight": (cols,),
              "output": (rows, cols)}
    rng = np.random.RandomState(2)
    x = rng.randn(rows, cols).astype(np.float32)
    w = rng.uniform(0.5, 1.5, cols).astype(np.float32)
    x64, w64 = x.astype(np.float64), w.astype(np.float64)
    want = (x64 / np.sqrt((x64 * x64).mean(-1, keepdims=True) + eps)
            * w64) / (1 + np.exp(-(x64 / np.sqrt(
                (x64 * x64).mean(-1, keepdims=True) + eps) * w64)))
    prog = build_chain(specs[0], shapes, mode="fused", pattern="resident")
    xp = np.pad(x, [(0, 0), (0, 128 - cols)])
    wp = np.pad(w, [(0, 128 - cols)])
    got = interpret(prog, {"input": xp, "weight": wp},
                    {"output": (rows, 128)})["output"][:, :cols]
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=2e-5)


def test_default_eps_is_elided_from_attrs():
    """The recipe-default eps must NOT enter the chain attrs — otherwise
    every declared rmsnorm fixture would fingerprint apart from its
    extracted re-derivation."""
    from repro.models import layers as L
    from repro.models.workloads import _CFG
    specs = extract_chains(
        lambda x, w: jax.nn.silu(L.apply_norm({"scale": w}, x, _CFG)),
        (("input", (4, 64)), ("weight", (64,))), name="eps_default")
    assert dict(specs[0].attrs) == {}


def test_conflicting_eps_in_one_component_qualifies_per_stage():
    """Two norms with different eps in ONE fusable component used to refuse
    outright; the proposer now qualifies each value as ``eps@<stage out>``
    so both stages keep their own eps (needed for traced VJP chains whose
    stages legitimately disagree on scalar attrs)."""
    from repro.models import layers as L
    from repro.models.workloads import _CFG
    specs = extract_chains(
        lambda x, w, w2: L.apply_norm(
            {"scale": w2},
            L.apply_norm({"scale": w}, x, _CFG, eps=1e-4),
            _CFG, eps=2e-4),
        (("input", (4, 64)), ("w", (64,)), ("w2", (64,))),
        name="eps_conflict")
    assert len(specs) == 1
    spec = specs[0]
    assert [st.op for st in spec.stages] == ["rmsnorm", "rmsnorm"]
    attrs = dict(spec.attrs)
    assert attrs[f"eps@{spec.stages[0].output}"] == pytest.approx(1e-4)
    assert attrs[f"eps@{spec.stages[1].output}"] == pytest.approx(2e-4)


# ---------------------------------------------------------------------------
# log_softmax / layernorm composite coverage (formerly barrier.<prim>)
# ---------------------------------------------------------------------------

def test_log_softmax_composite_recognized():
    spec = _single_chain(lambda x, b: jax.nn.log_softmax(x + b, axis=-1),
                         (("input", (4, 64)), ("bias", (64,))))
    assert [st.op for st in spec.stages] == ["add", "log_softmax"]
    assert dict(spec.pad_values) == {"input": -3.0e38}


def test_layernorm_composite_recognized():
    from repro.models import layers as L
    from repro.models.workloads import _LN_CFG
    spec = _single_chain(
        lambda x, r, w, b: L.apply_norm({"scale": w, "bias": b}, x + r,
                                        _LN_CFG),
        (("input", (4, 64)), ("residual", (4, 64)), ("weight", (64,)),
         ("bias", (64,))))
    assert [st.op for st in spec.stages] == ["add", "layernorm"]
    assert spec.stages[1].inputs == ("h", "weight", "bias")
    # apply_norm's layernorm eps default (1e-6) differs from the recipe
    # default (1e-5): it must be carried
    assert abs(dict(spec.attrs)["eps"] - 1e-6) < 1e-9


def test_new_extraction_chains_registered_end_to_end():
    """double_softmax (multi-stat), bias_log_softmax and add_layernorm are
    extraction-only chains: registered, planner-wired, tuner-searchable,
    fused-suite-covered."""
    from repro.bench.tasks import fused_suite
    from repro.core.planner import PLANNER_REGISTRY
    from repro.core.tuning import variants_for
    tasks = {t.name for t in fused_suite()}
    for name in ("double_softmax", "bias_log_softmax", "add_layernorm"):
        assert name in CHAINS
        assert CHAIN_SOURCES[name] == ("extracted",)
        assert name in PLANNER_REGISTRY
        assert f"{name}_streaming" in PLANNER_REGISTRY
        assert "fused" in variants_for(name)
        assert name in tasks
    assert [st.op for st in CHAINS["double_softmax"].stages] == \
        ["softmax", "softmax"]
    assert dict(CHAINS["double_softmax"].pad_values) == {
        "input": -3.0e38, "h": -3.0e38}


def test_weightless_rmsnorm_composite_recognized_and_builds():
    """Gap fix (DESIGN.md §13 satellite): x * rsqrt(mean(x*x) + eps) with
    NO learned gain — the normalization idiom of gain-free norm layers —
    collapses to an arity-1 rmsnorm stage instead of barriering on the
    bare reduce, and the built chain computes the weightless recipe."""
    spec = _single_chain(
        lambda x: jax.nn.silu(
            x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + 1e-6)),
        (("input", (4, 64)),), name="noweight_rmsnorm")
    assert [st.op for st in spec.stages] == ["rmsnorm", "silu"]
    assert [len(st.inputs) for st in spec.stages] == [1, 1]
    assert dict(spec.attrs) == {}            # default eps elided

    from repro.core.dsl.interp import interpret
    from repro.core.fusion import build_chain
    rows, cols = 4, 96
    rng = np.random.RandomState(3)
    x = rng.randn(rows, cols).astype(np.float32)
    x64 = x.astype(np.float64)
    h = x64 / np.sqrt((x64 * x64).mean(-1, keepdims=True) + 1e-6)
    want = h / (1 + np.exp(-h))
    prog = build_chain(spec, {"input": (rows, cols)}, mode="fused",
                       pattern="resident")
    xp = np.pad(x, [(0, 0), (0, 128 - cols)])
    got = interpret(prog, {"input": xp},
                    {"output": (rows, 128)})["output"][:, :cols]
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=2e-5)


def test_weightless_rmsnorm_non_default_eps_carried():
    """The traced eps of a weightless rmsnorm rides the chain attrs just
    like the weighted form's."""
    spec = _single_chain(
        lambda x: jax.nn.silu(
            x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + 2e-5)),
        (("input", (4, 64)),), name="noweight_eps")
    assert [st.op for st in spec.stages] == ["rmsnorm", "silu"]
    eps = dict(spec.attrs)["eps"]
    assert abs(eps - 2e-5) < 1e-10          # f32-rounded trace constant


def test_decode_attention_extracts_and_dedupes_onto_flash():
    """The scan-free single-token decode block (KV-cache write + GQA
    attention over the cached keys, traced VERBATIM from
    layers.apply_attention's decode branch) yields ONE chain spanning both
    cache contractions.  The vmapped dynamic_update_slice cache writes and
    the QKV/rope/output projections stay barriers — the updated caches
    re-enter the attention interior as plain chain inputs — and the
    derived chain is structurally IDENTICAL to flash_attention: its
    α-invariant fingerprint dedupes onto the registered chain, so the
    decode path rides the same generated kernel with zero registry
    churn."""
    w = W["decode_attention"]
    specs = extract_chains(w.fn, w.shapes, name=w.name)
    assert len(specs) == 1
    (spec,) = specs
    assert [st.op for st in spec.stages] == [
        "matmul_t", "scale", "add", "softmax", "matmul"]
    # decode trace head_dim=16 → qk scale 1/sqrt(16)
    assert abs(dict(spec.attrs)["scale"] - 0.25) < 1e-12
    assert chain_fingerprint(spec) == \
        chain_fingerprint(CHAINS["flash_attention"])
    # dedupe: no separate registry entry, flash already carries the
    # "extracted" source tag
    assert "decode_attention" not in CHAINS
    assert "extracted" in CHAIN_SOURCES["flash_attention"]


def test_decode_attention_cache_ops_are_barriers_not_swallowed():
    """The cache write (dynamic_update_slice under vmap → scatter-style
    update) must segment the graph, not vanish into the chain: the fused
    decode kernel reads the UPDATED cache, which is producible only if the
    update runs as a barrier whose output feeds the chain."""
    w = W["decode_attention"]
    graph = extract_graph(w.fn, w.shapes, name=w.name)
    ops = [n.op for n in graph.nodes]
    # the vmapped dynamic_update_slice cache writes trace as scatters
    assert ops.count("barrier.scatter") == 2           # k and v writes
    # the four projections (wq/wk/wv/wo) are unbatched h @ w dots and
    # stay barriers; BOTH cache contractions classify as stages
    assert ops.count("barrier.dot_general") == 4
    assert ops.count("matmul_t") == 1 and ops.count("matmul") == 1


# ---------------------------------------------------------------------------
# Backward-path stop_gradient aliasing (DESIGN.md §16): remat'd VJPs
# ---------------------------------------------------------------------------

def test_checkpointed_norm_vjp_extracts_and_dedupes():
    """VJP of the pre-norm residual block under jax.checkpoint: the
    transposed jaxpr re-runs the forward with the saved residuals wrapped
    in stop_gradient (remat).  The extractor must alias straight through
    those wrappers on the backward path — same rule as forward — so the
    checkpointed trace yields the SAME [rmsnorm_bwd, add] chain and
    fingerprint-dedupes onto norm_residual_bwd instead of refusing."""
    w = W["ckpt_norm_bwd"]
    specs = extract_chains(w.fn, w.shapes, name=w.name)
    assert specs, "checkpointed VJP extraction refused (stop_gradient)"
    ops = [[st.op for st in s.stages] for s in specs]
    assert ["rmsnorm_bwd", "add"] in ops, ops
    (spec,) = [s for s in specs
               if [st.op for st in s.stages] == ["rmsnorm_bwd", "add"]]
    assert chain_fingerprint(spec) == \
        chain_fingerprint(CHAINS["norm_residual_bwd"])
    # dedupe means NO separate ckpt chain got registered
    assert not any(n.startswith("ckpt_norm") for n in CHAINS)
    assert CHAIN_SOURCES["norm_residual_bwd"] == ("extracted",)
