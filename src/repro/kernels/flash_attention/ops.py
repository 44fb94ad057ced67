"""jit'd public wrapper for flash attention.

Forward runs the GENERATED fusion chain: the proposer derives the
flash-attention recipe (qk^T matmul -> scale -> mask-add -> online
softmax -> pv matmul) from the traced ``mha_reference`` itself
(``models/workloads.py``), and ``build_fused`` stitches it into one
streaming kernel with loop-carried (m, l, acc) state — the hand-written
Pallas kernel this module used to import is gone (DESIGN.md §13).
Backward is a custom VJP through the reference implementation with
recompute (flash-style: no attention matrix is saved).  Model code selects
`impl="pallas" | "xla"`; the CPU dry-run uses "xla" so the compiled HLO and
cost analysis reflect what XLA will run (DESIGN.md §7).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .ref import mha_reference


# --------------------------------------------------------------------------
# Generated-chain forward.  The chain is derived per 2-D (seq, head_dim)
# slice; build_chain specializes column extents into the kernel AST, so we
# build-and-cache one program per distinct (Sq, Skv, D) and loop the
# (batch, head) grid over it.  GQA maps q-head h -> kv-head h // group.
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _chain_entry(Sq: int, Skv: int, D: int):
    """Compile the fused flash chain at one slice geometry.

    Returns (entry, baked_scale): `entry(q2, k2, mask, v2)` computes
    softmax(q2 @ k2.T * baked_scale + mask) @ v2 with f32 accumulation
    (streaming online-softmax when the row does not fit VMEM, resident
    single-visit otherwise; sequential staging if fusion refuses).
    """
    from ...core.fusion.chain import CHAINS, build_fused
    from ...core.lowering.pipeline import transcompile
    spec = CHAINS["flash_attention"]
    shapes = {"q": (Sq, D), "k": (Skv, D), "mask": (Sq, Skv),
              "v": (Skv, D), "output": (Sq, D)}
    prog = build_fused(spec, shapes)
    art = transcompile(prog, verify_against_interp=False)
    return art.entry, float(dict(spec.attrs)["scale"])


# The chain lowers to the pipelined (BlockSpec) backend, the form Mosaic
# compiles, only at lane-aligned row and key lengths; other lengths pad up.
_ALIGN = 128


def _round_up(n: int) -> int:
    return -(-n // _ALIGN) * _ALIGN


def _mask(Sq: int, Skv: int, causal: bool):
    """Additive mask at the padded geometry.  Causal is bottom-right
    aligned (decode-friendly): query i attends keys <= i + (Skv - Sq).
    Padded keys take -3e38, the chain's mask pad sentinel: finite,
    exp-underflows to exactly 0 like -inf, and survives the online-softmax
    rescale without NaNs.  Padded query rows attend the real keys and are
    sliced off."""
    shape = (_round_up(Sq), _round_up(Skv))
    qi = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + (Skv - Sq)
    ki = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    live = ki < Skv
    if causal:
        live = live & (qi >= ki)
    return jnp.where(live, 0.0, -3.0e38).astype(jnp.float32)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        sm_scale: float | None = None):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D).  Returns (B, Sq, Hq, D).

    Runs the generated fused chain per (batch, q-head) slice.  The chain
    bakes the qk scale traced from the reference; an arbitrary `sm_scale`
    is folded into q up front (q' @ k^T * baked == q @ k^T * sm_scale).
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    entry, baked = _chain_entry(_round_up(Sq), _round_up(Skv), D)

    def pad_seq(x, s):
        x = jnp.asarray(x, jnp.float32)
        return jnp.pad(x, ((0, 0), (0, _round_up(s) - s), (0, 0), (0, 0)))

    qf = pad_seq(q, Sq) * (sm_scale / baked)
    kf = pad_seq(k, Skv)
    vf = pad_seq(v, Skv)
    mask = _mask(Sq, Skv, causal)

    batches = []
    for b in range(B):
        heads = [entry(qf[b, :, h, :], kf[b, :, h // group, :], mask,
                       vf[b, :, h // group, :])
                 for h in range(Hq)]
        batches.append(jnp.stack(heads, axis=1))       # (Sqp, Hq, D)
    out = jnp.stack(batches, axis=0)[:, :Sq]           # (B, Sq, Hq, D)
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: float | None = None):
    return flash_attention_fwd(q, k, v, causal=causal, sm_scale=sm_scale)


def _fwd(q, k, v, causal, sm_scale):
    out = flash_attention_fwd(q, k, v, causal=causal, sm_scale=sm_scale)
    return out, (q, k, v)


def _bwd(causal, sm_scale, res, g):
    q, k, v = res
    # recompute-based VJP through the reference (flash-style backward)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: mha_reference(q_, k_, v_, causal=causal,
                                         sm_scale=sm_scale), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


def attention(q, k, v, *, causal: bool = True, sm_scale=None,
              impl: str = "auto", logit_cap: float = 0.0):
    """Framework entry point; `impl` in {"auto", "pallas", "xla"}."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas" and logit_cap == 0.0:
        return flash_attention(q, k, v, causal, sm_scale)
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                         logit_cap=logit_cap)


# decode path (single token vs KV cache) — reference impl is the XLA path
from .ref import decode_reference as mha_decode  # noqa: E402


def decode_attention_fused(q, k_cache, v_cache, cache_len, *,
                           sm_scale=None):
    """Single-token decode over the KV cache through the GENERATED chain.

    q: (B, 1, Hq, D); caches: (B, S, Hkv, D); cache_len: (B,) int32.
    Returns (B, 1, Hq, D).  The decode-step extraction dedupes onto the
    flash_attention chain (DESIGN.md §15), so the same cached 2-D kernel
    serves decode: each (batch, kv-head) slice runs the chain at
    Sq = group rows (the GQA query group attending that kv-head) with the
    causal mask replaced by the per-slot additive LENGTH mask
    where(pos < cache_len[b], 0, -3e38) — padded / not-yet-written cache
    positions exp-underflow to exactly 0, matching decode_reference.
    """
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)

    entry, baked = _chain_entry(group, S, D)
    # (B, 1, Hq, D) -> (B, Hkv, group, D): heads are consecutive blocks
    qf = (jnp.asarray(q, jnp.float32) * (sm_scale / baked)) \
        .reshape(B, Hkv, group, D)
    kf = jnp.asarray(k_cache, jnp.float32)
    vf = jnp.asarray(v_cache, jnp.float32)
    lens = jnp.asarray(cache_len, jnp.int32)
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    length_mask = jnp.where(pos < lens[:, None], 0.0, -3.0e38) \
        .astype(jnp.float32)                            # (B, S)

    batches = []
    for b in range(B):
        mask_b = jnp.broadcast_to(length_mask[b][None, :], (group, S))
        heads = [entry(qf[b, j], kf[b, :, j, :], mask_b, vf[b, :, j, :])
                 for j in range(Hkv)]                   # each (group, D)
        batches.append(jnp.concatenate(heads, axis=0))  # (Hq, D)
    out = jnp.stack(batches, axis=0)[:, None]           # (B, 1, Hq, D)
    return out.astype(q.dtype)
