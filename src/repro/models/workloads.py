"""Traceable framework hot spots — the fusion extractor's source of truth.

Each :class:`Workload` names a real model computation (a block function or
the inter-matmul segment of one) as a plain JAX function plus example
trace shapes.  ``core/fusion/extract.py`` traces these with
``jax.make_jaxpr``, normalizes the jaxpr into the proposer's OpGraph IR
and derives fusable chains from them (DESIGN.md §11) — the hand-declared
``GRAPHS`` tuple in ``fusion/propose.py`` survives only as golden
fixtures that this library must re-derive.

The functions deliberately reuse the *actual* layer implementations where
one exists (``layers.apply_norm``, ``layers.apply_mlp``,
``layers.apply_attention``, the flash-attention reference) so the
extractor is exercised against the primitives real model code emits —
including matmul/rope/reshape barriers and the ``where(mask, logits,
-inf)`` masking idiom — not against hand-massaged toy graphs.  Argument
names align with the golden fixtures' tensor names; for chains the
fixtures do not cover, canonical naming comes from
``extract.canonicalize_spec``.

Trace shapes are tiny: extraction only reads dataflow *structure* (ops,
ranks, broadcast roles), never sizes — the planner/tuner re-instantiates
chains at real task shapes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ArchConfig
from ..kernels.flash_attention.ref import mha_reference


@dataclass(frozen=True)
class Workload:
    name: str
    fn: Callable
    shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]   # (arg, trace shape)
    doc: str = ""


# a minimal rmsnorm config for apply_norm (structure-only: sizes are the
# trace shapes below, never this config's)
# attention traces as the XLA reference on every backend: on a TPU, "auto"
# would trace the generated flash chain, which needs the very chains this
# extraction derives
_CFG = ArchConfig(name="trace", n_layers=1, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=64, norm="rmsnorm",
                  attn_impl="xla")
# the same config in its layernorm variant (post-LN blocks)
_LN_CFG = ArchConfig(name="trace_ln", n_layers=1, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=64, norm="layernorm")
# decode trace config: full-precision KV cache so the single-token decode
# block traces the fp32 attention interior (the int8 default adds
# quantize/dequantize barriers around the same chain)
_DEC_CFG = ArchConfig(name="trace_decode", n_layers=1, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab=64, norm="rmsnorm",
                      kv_cache_dtype="model", dtype="float32")

_B, _S, _D, _FF = 2, 16, 64, 128


# --------------------------------------------------------------------------
# Inter-matmul segments (the six golden chains)
# --------------------------------------------------------------------------

def _bias_gelu(input, bias):                       # noqa: A002
    # biased up-projection epilogue: the model's gelu MLP activation
    # (layers.apply_mlp kind="gelu") applied to a bias-carrying dense out
    return jax.nn.gelu(input + bias, approximate=True)


def _mul_softmax(input, scale):                    # noqa: A002
    # per-column scaled (temperature) softmax
    return jax.nn.softmax(input * scale, axis=-1)


def _rmsnorm_swiglu(input, weight, gate):          # noqa: A002
    # rmsnorm feeding a gated activation (layers.apply_norm is the real
    # model norm; the gate branch arrives from a matmul upstream)
    h = L.apply_norm({"scale": weight}, input, _CFG)
    return jax.nn.silu(h) * gate


def _add_rmsnorm(input, residual, weight, w_gate, w_up, w_down):  # noqa: A002
    # the REAL pre-FFN segment of models/transformer._apply_layer: the
    # residual stream update + norm, flanked by the FFN matmuls.  The
    # matmuls are barriers AND close a cycle (the FFN output is added back
    # onto the residual stream), so the proposer must stop the chain at
    # {add, rmsnorm} with the updated residual escaping — exactly the
    # declared add_rmsnorm fixture.
    new_residual = input + residual
    h = L.apply_norm({"scale": weight}, new_residual, _CFG)
    out = L.apply_mlp({"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                      h, "swiglu")
    return new_residual + out


def _attn_scores(input, scale, mask):              # noqa: A002
    # attention score pipeline with per-column scale and additive mask
    # (ALiBi-style), rows far too wide for VMEM residency at bench shapes
    return jax.nn.softmax(input * scale + mask, axis=-1)


def _swiglu_proj(input, gate_scale, up_scale):     # noqa: A002
    # two-branch gated activation over per-column-scaled projections of
    # the SAME input (shared producer -> DAG chain)
    return jax.nn.silu(input * gate_scale) * (input * up_scale)


def _double_softmax(input):                        # noqa: A002
    # two-level score re-normalization (hierarchical / doubly-normalized
    # attention): softmax over softmax — TWO loop-carried stat stages,
    # fusable only through the per-stat spill schedule (DESIGN.md §12)
    return jax.nn.softmax(jax.nn.softmax(input, axis=-1), axis=-1)


def _bias_log_softmax(input, bias):                # noqa: A002
    # LM-head epilogue: biased logits -> log-probabilities (the
    # cross-entropy input); exercises the log_softmax composite
    return jax.nn.log_softmax(input + bias, axis=-1)


def _add_layernorm(input, residual, weight, bias): # noqa: A002
    # post-LN residual block: LN(x + sublayer(x)) with the model's real
    # layernorm (apply_norm traces with its eps, which rides the
    # composite's attrs into the chain recipe)
    return L.apply_norm({"scale": weight, "bias": bias}, input + residual,
                        _LN_CFG)


# --------------------------------------------------------------------------
# Real block functions (new chains + end-to-end validation)
# --------------------------------------------------------------------------

def _mask_softmax(input, mask):                    # noqa: A002
    # additively-masked score normalization — the inter-matmul segment of
    # attention on its own (padding masks, cross-attention biases).  Keeps
    # the mask_softmax chain registered in its 2-stage form now that the
    # full attention reference extracts THROUGH the matmuls.
    return jax.nn.softmax(input + mask, axis=-1)


def _attention_probs(q, k, v):
    # the flash-attention REFERENCE (the exact path CPU model code runs):
    # qk^T matmul -> scalar scale -> where(causal, logits, -inf) ->
    # softmax -> pv matmul.  The extractor canonicalizes the masked fill
    # into the additive-mask idiom and — since the matmul stage template —
    # classifies both contractions as fusable stages, deriving the
    # flash_attention chain (matmul_t -> scale -> add -> softmax ->
    # matmul) as ONE chain across the former matmul barriers.
    return mha_reference(q, k, v, causal=True)


def _transformer_block(x, norm1_w, wq, wk, wv, wo, norm2_w,
                       w_gate, w_up, w_down):
    # models/transformer._apply_layer, non-mHC path, verbatim structure:
    # pre-norm attention + residual, pre-norm swiglu MLP + residual.
    # Validation workload: every chain extracted here must fingerprint-
    # dedupe onto an already-registered chain (mask_softmax from the
    # attention scores, add_rmsnorm from the pre-FFN segment).
    h = L.apply_norm({"scale": norm1_w}, x, _CFG)
    attn, _ = L.apply_attention(
        {"wq": wq, "wk": wk, "wv": wv, "wo": wo}, h, _CFG)
    x = x + attn
    h2 = L.apply_norm({"scale": norm2_w}, x, _CFG)
    out = L.apply_mlp({"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                      h2, "swiglu")
    return x + out


def _decode_attention(x, wq, wk, wv, wo, k_cache, v_cache, length):
    # the scan-free single-token attention block of transformer.decode_step
    # (models/layers.apply_attention, decode branch), traced VERBATIM: QKV
    # projections + rope (barriers), the vmapped `dynamic_update_slice`
    # cache writes (barriers whose outputs — the updated caches — re-enter
    # the chain as plain inputs), GQA attention over the cached keys with
    # the `where(pos < length, logits, -inf)` length mask, and the output
    # projection (barrier).  The extractor canonicalizes the masked fill
    # into the additive-mask idiom and classifies both cache contractions
    # as matmul_t/matmul stages, so the proposer derives the decode
    # attention chain (matmul_t -> scale -> add -> softmax -> matmul) —
    # structurally IDENTICAL to flash_attention, onto whose fingerprint it
    # dedupes (DESIGN.md §15).  ``length`` traces as f32 (the extractor
    # traces every arg as f32) and is cast back to the cache's int32
    # index dtype inside.
    idx = length.astype(jnp.int32)
    out, new_cache = L.apply_attention(
        {"wq": wq, "wk": wk, "wv": wv, "wo": wo}, x, _DEC_CFG,
        positions=idx[:, None],
        cache={"k": k_cache, "v": v_cache, "length": idx})
    return out, new_cache["k"], new_cache["v"]


# --------------------------------------------------------------------------
# Backward passes (DESIGN.md §16): jax.vjp through the SAME layer
# implementations, traced so the extractor sees the transposed-jaxpr idioms
# real training emits — cotangent broadcasts, mul-chains over saved forward
# residuals, and row-axis reduce_sums.  The rewriter folds these into the
# *_bwd composites (rmsnorm_bwd / softmax_bwd / log_softmax_bwd) and the
# proposer derives backward chains from them exactly like forward ones.
# --------------------------------------------------------------------------

def _norm_residual_bwd(x, weight, g):
    # input gradient of the pre-norm residual block y = x + norm(x): the
    # transposed jaxpr interleaves the residual cotangent INTO the
    # rmsnorm_bwd add-tree; the matcher re-materializes it as a trailing
    # add, deriving the [rmsnorm_bwd, add] chain
    _, vjp = jax.vjp(
        lambda xx: xx + L.apply_norm({"scale": weight}, xx, _CFG), x)
    return vjp(g)[0]


def _ckpt_norm_bwd(x, weight, g):
    # the SAME block under jax.checkpoint (gradient rematerialization):
    # the VJP jaxpr re-runs the forward under remat2/stop_gradient
    # wrapping, which the extractor aliases through on the backward path
    # just like forward.  Must fingerprint-dedupe onto norm_residual_bwd.
    f = jax.checkpoint(
        lambda xx: xx + L.apply_norm({"scale": weight}, xx, _CFG))
    _, vjp = jax.vjp(f, x)
    return vjp(g)[0]


def _mlp_bwd(x, w_gate, w_up, w_down, g):
    # input gradient through the real swiglu MLP: the transposed matmuls
    # are barriers, leaving the silu-backward interior (sigmoid mul-chain
    # from the product rule over the saved gate residual) and the two-
    # branch cotangent merge as the extractable inter-matmul segments
    _, vjp = jax.vjp(
        lambda xx: L.apply_mlp(
            {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
            xx, "swiglu"), x)
    return vjp(g)[0]


def _attn_scores_bwd(z, mask, g):
    # score gradient of masked attention probabilities: softmax_bwd's
    # transposed form (y * (g - rowsum(g * y)) recomputed from the saved
    # exp/denominator residuals) behind the forward mask add
    _, vjp = jax.vjp(lambda x: jax.nn.softmax(x + mask, axis=-1), z)
    return vjp(g)[0]


def _lm_head_bwd(z, bias, g):
    # logit gradient of the LM-head epilogue: log_softmax_bwd
    # (g - softmax(z) * rowsum(g)) behind the forward bias add
    _, vjp = jax.vjp(lambda x: jax.nn.log_softmax(x + bias, axis=-1), z)
    return vjp(g)[0]


def _ce_grad(logits, onehot):
    # fused loss+grad: the manual stable-logsumexp cross entropy with a
    # stop_gradient'd max shift (the idiom training code writes by hand).
    # KNOWN PARTIAL COVERAGE (DESIGN.md §16): the loss and grad branches
    # share the exp/reduce_sum residuals, so neither the log_softmax nor
    # the log_softmax_bwd composite can claim them — extraction still
    # yields the map-only epilogue chain, and the stop_gradient wrapping
    # exercises the backward aliasing rule.
    def loss(lg):
        m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
        logz = jnp.squeeze(m, -1) + jnp.log(jnp.sum(jnp.exp(lg - m), -1))
        gold = jnp.sum(onehot * lg, axis=-1)
        return jnp.sum(logz - gold)
    return jax.value_and_grad(loss)(logits)


def _mhc_stream_bwd(M, beta, g):
    # backward of the mhc_post stream mixer (models/layers.mhc_post) in
    # its per-stream decomposed form: dh[j] = sum_i M[i,j] * g[i] and
    # do = sum_i beta[i] * g[i].  The einsum form is a single opaque
    # barrier; decomposed, every stream product is an smul (dynamic
    # scalar multiply) and the extractor derives the smul/add mixing
    # chain — all five trees (4 dh streams + do) fingerprint-dedupe onto
    # ONE registered chain, the building block kernels/mhc_bwd.py
    # assembles into the derived mhc_post_grad.
    gs = [g[:, i, :] for i in range(4)]
    dh = [sum(M[i, j] * gs[i] for i in range(4)) for j in range(4)]
    do = sum(beta[i] * gs[i] for i in range(4))
    return jnp.stack(dh, axis=1), do


_HD = _CFG.resolved_head_dim

WORKLOADS: Tuple[Workload, ...] = (
    Workload("bias_gelu", _bias_gelu,
             (("input", (_B * _S, _FF)), ("bias", (_FF,))),
             doc="biased FFN up-projection epilogue"),
    Workload("mul_softmax", _mul_softmax,
             (("input", (_S, _S)), ("scale", (_S,))),
             doc="temperature/column-scaled softmax"),
    Workload("rmsnorm_swiglu", _rmsnorm_swiglu,
             (("input", (_B * _S, _D)), ("weight", (_D,)),
              ("gate", (_B * _S, _D))),
             doc="model norm feeding a gated activation"),
    Workload("add_rmsnorm", _add_rmsnorm,
             (("input", (_B * _S, _D)), ("residual", (_B * _S, _D)),
              ("weight", (_D,)), ("w_gate", (_D, _FF)),
              ("w_up", (_D, _FF)), ("w_down", (_FF, _D))),
             doc="residual update + norm inside the real FFN block"),
    Workload("attn_scores", _attn_scores,
             (("input", (_S, _S)), ("scale", (_S,)), ("mask", (_S,))),
             doc="scaled + additively-masked attention scores"),
    Workload("swiglu_proj", _swiglu_proj,
             (("input", (_B * _S, _D)), ("gate_scale", (_D,)),
              ("up_scale", (_D,))),
             doc="two-branch gated projection (shared producer DAG)"),
    Workload("double_softmax", _double_softmax,
             (("input", (_S, _S)),),
             doc="two-level score re-normalization (multi-stat chain)"),
    Workload("bias_log_softmax", _bias_log_softmax,
             (("input", (_B * _S, _D)), ("bias", (_D,))),
             doc="LM-head epilogue: biased logits -> log-probabilities"),
    Workload("add_layernorm", _add_layernorm,
             (("input", (_B * _S, _D)), ("residual", (_B * _S, _D)),
              ("weight", (_D,)), ("bias", (_D,))),
             doc="post-LN residual block (traced non-default eps)"),
    Workload("mask_softmax", _mask_softmax,
             (("input", (_S, _S)), ("mask", (_S, _S))),
             doc="additively-masked score normalization"),
    Workload("flash_attention", _attention_probs,
             (("q", (_B, _S, _CFG.n_heads, _HD)),
              ("k", (_B, _S, _CFG.n_kv_heads, _HD)),
              ("v", (_B, _S, _CFG.n_kv_heads, _HD))),
             doc="flash-attention reference: the full masked-attention "
                 "chain through both matmuls"),
    Workload("decode_attention", _decode_attention,
             (("x", (_B, 1, _D)),
              ("wq", (_D, _CFG.n_heads * _HD)),
              ("wk", (_D, _CFG.n_kv_heads * _HD)),
              ("wv", (_D, _CFG.n_kv_heads * _HD)),
              ("wo", (_CFG.n_heads * _HD, _D)),
              ("k_cache", (_B, _S, _CFG.n_kv_heads, _HD)),
              ("v_cache", (_B, _S, _CFG.n_kv_heads, _HD)),
              ("length", (_B,))),
             doc="single-token decode-step attention over the KV cache "
                 "(cache read/update as chain inputs/outputs; dedupes "
                 "onto flash_attention)"),
    Workload("transformer_block", _transformer_block,
             (("x", (_B, _S, _D)), ("norm1_w", (_D,)),
              ("wq", (_D, _CFG.n_heads * _HD)),
              ("wk", (_D, _CFG.n_kv_heads * _HD)),
              ("wv", (_D, _CFG.n_kv_heads * _HD)),
              ("wo", (_CFG.n_heads * _HD, _D)),
              ("norm2_w", (_D,)), ("w_gate", (_D, _FF)),
              ("w_up", (_D, _FF)), ("w_down", (_FF, _D))),
             doc="full pre-norm transformer layer (validation: all chains "
                 "must dedupe onto registered fingerprints)"),
    # ---- backward passes (DESIGN.md §16) ---------------------------------
    Workload("norm_residual_bwd", _norm_residual_bwd,
             (("x", (_B * _S, _D)), ("weight", (_D,)),
              ("g", (_B * _S, _D))),
             doc="VJP of the pre-norm residual block: rmsnorm_bwd + "
                 "residual cotangent add"),
    Workload("ckpt_norm_bwd", _ckpt_norm_bwd,
             (("x", (_B * _S, _D)), ("weight", (_D,)),
              ("g", (_B * _S, _D))),
             doc="the same VJP under jax.checkpoint (dedupes onto "
                 "norm_residual_bwd)"),
    Workload("mlp_bwd", _mlp_bwd,
             (("x", (_B * _S, _D)), ("w_gate", (_D, _FF)),
              ("w_up", (_D, _FF)), ("w_down", (_FF, _D)),
              ("g", (_B * _S, _D))),
             doc="VJP through the real swiglu MLP: silu-backward interior "
                 "+ two-branch cotangent merge"),
    Workload("attn_scores_bwd", _attn_scores_bwd,
             (("z", (_S, _S)), ("mask", (_S, _S)), ("g", (_S, _S))),
             doc="VJP of masked attention probabilities (softmax_bwd)"),
    Workload("lm_head_bwd", _lm_head_bwd,
             (("z", (_B * _S, _D)), ("bias", (_D,)), ("g", (_B * _S, _D))),
             doc="VJP of the LM-head epilogue (log_softmax_bwd)"),
    Workload("ce_grad", _ce_grad,
             (("logits", (_S, _D)), ("onehot", (_S, _D))),
             doc="fused stable-CE loss+grad pair (known partial coverage, "
                 "stop_gradient aliasing)"),
    Workload("mhc_stream_bwd", _mhc_stream_bwd,
             (("M", (4, 4)), ("beta", (4,)), ("g", (_B * 4, 4, _S))),
             doc="per-stream decomposed mhc_post backward: the smul/add "
                 "mixing chain mhc_post_grad re-derives from"),
)
