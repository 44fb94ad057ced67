"""Layer library — pure-functional (params-as-pytrees) building blocks.

Covers every assigned architecture family:
  * GQA attention (+ optional qk-norm), RoPE
  * MLA (DeepSeek-V2 compressed-KV attention)
  * SwiGLU / GELU MLPs
  * MoE with shared experts + top-k routing (dense dispatch; EP-shardable)
  * Mamba selective-SSM block (associative-scan train/prefill, stateful decode)
  * mLSTM / sLSTM blocks (xLSTM)
  * optional mHC hyper-connection residual streams (paper RQ3 feature)

Conventions: params are nested dicts of jnp arrays; `init_*` take a
jax.random key and a config; `apply_*` are shape-polymorphic and
dtype-preserving (compute in f32 where numerics demand, cast back).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ArchConfig, LayerSpec
from ..kernels.flash_attention import ops as fa_ops


def _dense_init(key, in_dim, out_dim, dtype, scale=None):
    scale = scale if scale is not None else (1.0 / math.sqrt(in_dim))
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p, x, cfg: ArchConfig, eps=1e-6):
    # multi-pod SPMD: the f32 upcast + scale broadcast is where XLA's
    # propagation used to flip the activation layout and pay an
    # involuntary full remat; pin the canonical layout at the boundary
    # (no-op outside a mesh context)
    from ..distributed.sharding import constrain_activation
    xf = constrain_activation(x.astype(jnp.float32))
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return constrain_activation(out.astype(x.dtype))


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float, positions):
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (S, D/2) or (B, S, D/2)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * cos - xf2 * sin,
                            xf2 * cos + xf1 * sin], -1).astype(x.dtype)


# --------------------------------------------------------------------------
# GQA attention (+ qk-norm)
# --------------------------------------------------------------------------

def init_attention(key, cfg: ArchConfig):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(k1, d, cfg.n_heads * hd, dt),
        "wk": _dense_init(k2, d, cfg.n_kv_heads * hd, dt),
        "wv": _dense_init(k3, d, cfg.n_kv_heads * hd, dt),
        "wo": _dense_init(k4, cfg.n_heads * hd, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _qk_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


def apply_attention(p, x, cfg: ArchConfig, *, positions=None, cache=None):
    """x: (B, S, d).  cache: None (train/prefill) or dict(k, v, length) for
    decode.  Returns (out, new_cache)."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"])
        k = _qk_norm(k, p["k_norm"])
    if positions is None:
        positions = jnp.arange(S)
    cos, sin = rope_freqs(hd, cfg.rope_theta, positions)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cache is None:
        out = fa_ops.attention(q, k, v, causal=cfg.causal,
                               impl=cfg.attn_impl)
        new_cache = None
    else:
        # decode: S == 1; write k/v at position `length`, attend over cache
        idx = cache["length"]                      # (B,) int32

        def upd(c, u, i):
            return jax.vmap(lambda c_, u_, i_: jax.lax.dynamic_update_slice(
                c_, u_.astype(c_.dtype), (i_,) + (0,) * (c_.ndim - 1)))(
                    c, u, i)

        if cfg.kv_cache_dtype == "int8":
            # per-(position, head) max-abs int8 quantization: halves the
            # dominant decode memory term (§Perf iteration 2)
            kq, ks = _q8(k)
            vq, vs = _q8(v)
            k_cache = upd(cache["k"], kq, idx)
            v_cache = upd(cache["v"], vq, idx)
            k_sc = upd(cache["k_scale"], ks, idx)
            v_sc = upd(cache["v_scale"], vs, idx)
            k_full = k_cache.astype(jnp.float32) * k_sc[..., None]
            v_full = v_cache.astype(jnp.float32) * v_sc[..., None]
            out = fa_ops.mha_decode(q.astype(jnp.float32), k_full, v_full,
                                    idx + 1)
            new_cache = {"k": k_cache, "v": v_cache, "k_scale": k_sc,
                         "v_scale": v_sc, "length": idx + 1}
        else:
            k_cache = upd(cache["k"], k, idx)
            v_cache = upd(cache["v"], v, idx)
            out = fa_ops.mha_decode(q, k_cache, v_cache, idx + 1)
            new_cache = {"k": k_cache, "v": v_cache, "length": idx + 1}
    out = out.reshape(B, S, cfg.n_heads * hd).astype(x.dtype)
    return (out @ p["wo"]).astype(x.dtype), new_cache


def _q8(x):
    """Quantize (B, S, H, D) to int8 with per-(B, S, H) max-abs scales."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)         .astype(jnp.int8)
    return q, scale


def init_attention_cache(cfg: ArchConfig, batch: int, max_len: int,
                         dtype=None):
    hd = cfg.resolved_head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, max_len, cfg.n_kv_heads),
                                 jnp.float32),
            "v_scale": jnp.zeros((batch, max_len, cfg.n_kv_heads),
                                 jnp.float32),
            "length": jnp.zeros((batch,), jnp.int32),
        }
    dt = jnp.dtype(dtype or cfg.dtype)
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dt),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dt),
        "length": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV + decoupled RoPE key
# --------------------------------------------------------------------------

def init_mla(key, cfg: ArchConfig):
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    nh = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": _dense_init(ks[0], d, nh * (dn + dr), dt),
        "wkv_a": _dense_init(ks[1], d, cfg.kv_lora + dr, dt),   # down-proj
        "kv_norm": jnp.ones((cfg.kv_lora,), jnp.float32),
        "wkv_b": _dense_init(ks[2], cfg.kv_lora, nh * (dn + dv), dt),
        "wo": _dense_init(ks[3], nh * dv, d, dt),
    }


def apply_mla(p, x, cfg: ArchConfig, *, positions=None, cache=None):
    """MLA attention.  cache (decode): compressed c_kv + k_pe per position —
    the memory win that motivates MLA (cache is (kv_lora + rope_dim) wide
    instead of 2 * n_kv * head_dim)."""
    B, S, d = x.shape
    nh = cfg.n_heads
    dn, dr, dv = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.arange(S)

    q = (x @ p["wq"]).reshape(B, S, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    cos, sin = rope_freqs(dr, cfg.rope_theta, positions)
    q_pe = apply_rope(q_pe, cos, sin)

    kv_a = x @ p["wkv_a"]                           # (B, S, kv_lora + dr)
    c_kv, k_pe = kv_a[..., :cfg.kv_lora], kv_a[..., cfg.kv_lora:]
    c_kv = (c_kv.astype(jnp.float32)
            * jax.lax.rsqrt((c_kv.astype(jnp.float32) ** 2)
                            .mean(-1, keepdims=True) + 1e-6)
            * p["kv_norm"]).astype(x.dtype)
    k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)   # (B, S, 1, dr)

    if cache is not None:
        idx = cache["length"]
        c_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0)))(cache["c_kv"], c_kv, idx)
        pe_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0)))(cache["k_pe"], k_pe[:, :, 0, :], idx)
        c_kv_full, k_pe_full = c_cache, pe_cache[:, :, None, :]
        kv_len = cache["c_kv"].shape[1]      # static cache capacity
        mask_len = idx + 1
        new_cache = {"c_kv": c_cache, "k_pe": pe_cache, "length": idx + 1}
    else:
        c_kv_full, k_pe_full = c_kv, k_pe
        kv_len = S
        mask_len = None
        new_cache = None

    kv = (c_kv_full @ p["wkv_b"]).reshape(B, kv_len, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe_full, (B, kv_len, nh, dr))], -1)
    qh = jnp.concatenate([q_nope, q_pe], -1)

    sm_scale = 1.0 / math.sqrt(dn + dr)
    if cache is None:
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
        qi = jnp.arange(S)[:, None]
        ki = jnp.arange(kv_len)[None, :]
        logits = jnp.where((qi >= ki)[None, None], logits, -jnp.inf)
        prob = jax.nn.softmax(logits, -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", prob, v.astype(jnp.float32))
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh.astype(jnp.float32),
                            k.astype(jnp.float32)) * sm_scale
        ki = jnp.arange(kv_len)[None, None, None, :]
        logits = jnp.where(ki < mask_len[:, None, None, None], logits,
                           -jnp.inf)
        prob = jax.nn.softmax(logits, -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", prob, v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, -1, nh * dv)
    return out @ p["wo"], new_cache


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=None):
    dt = jnp.dtype(dtype or cfg.dtype)
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora), dt),
        "k_pe": jnp.zeros((batch, max_len, cfg.rope_head_dim), dt),
        "length": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, kind: str, d_ff: Optional[int] = None):
    d = cfg.d_model
    dff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"w_gate": _dense_init(ks[0], d, dff, dt),
                "w_up": _dense_init(ks[1], d, dff, dt),
                "w_down": _dense_init(ks[2], dff, d, dt)}
    return {"w_up": _dense_init(ks[0], d, dff, dt),
            "w_down": _dense_init(ks[1], dff, d, dt)}


def apply_mlp(p, x, kind: str):
    if kind == "swiglu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"], approximate=True) @ p["w_down"]


# --------------------------------------------------------------------------
# MoE (top-k routing, shared experts; experts stacked for EP sharding)
# --------------------------------------------------------------------------

def init_moe(key, cfg: ArchConfig):
    d = cfg.d_model
    dff = cfg.d_ff_expert or cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    E = cfg.n_experts

    def experts(k, n):
        k1, k2, k3 = jax.random.split(k, 3)
        s = 1.0 / math.sqrt(d)
        return {
            "w_gate": (jax.random.normal(k1, (n, d, dff), jnp.float32) * s
                       ).astype(dt),
            "w_up": (jax.random.normal(k2, (n, d, dff), jnp.float32) * s
                     ).astype(dt),
            "w_down": (jax.random.normal(k3, (n, dff, d), jnp.float32)
                       * (1.0 / math.sqrt(dff))).astype(dt),
        }

    p = {"router": _dense_init(ks[0], d, E, jnp.float32, scale=0.02),
         "experts": experts(ks[1], E)}
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[2], cfg, "swiglu",
                               dff * cfg.n_shared_experts)
    return p


def apply_moe(p, x, cfg: ArchConfig):
    if getattr(cfg, "moe_impl", "capacity") == "dense":
        return apply_moe_dense(p, x, cfg)
    return apply_moe_capacity(p, x, cfg)


def apply_moe_dense(p, x, cfg: ArchConfig):
    """Dense dispatch MoE: every expert processes every token, masked by the
    routing weights.  Simple and collective-free but O(E) FLOPs — kept as
    the reference implementation (§Perf iteration 3 replaced it with
    capacity dispatch as the default)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = (x.astype(jnp.float32) @ p["router"])           # (B, S, E)
    topv, topi = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1)                     # (B, S, k)
    # combine into per-expert weights (B, S, E), zero off the top-k
    w = jnp.zeros_like(logits).at[
        jnp.arange(B)[:, None, None], jnp.arange(S)[None, :, None], topi
    ].set(gates)

    def one_expert(wg, wu, wd):
        h = jax.nn.silu(x @ wg) * (x @ wu)
        return h @ wd                                          # (B, S, d)

    y = jnp.einsum(
        "ebsd,bse->bsd",
        jax.vmap(one_expert)(p["experts"]["w_gate"], p["experts"]["w_up"],
                             p["experts"]["w_down"]),
        w.astype(x.dtype))
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, "swiglu")
    return y.astype(x.dtype)


def _maybe_constrain(x, spec_axes):
    """with_sharding_constraint when a mesh context is active; no-op when
    running meshless (unit tests, single device)."""
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(x, P(*spec_axes))
    except Exception:  # noqa: BLE001 — no mesh / missing axis
        return x


def apply_moe_capacity(p, x, cfg: ArchConfig,
                       capacity_factor: float = 1.25):
    """Capacity-bucketed sparse dispatch (SPerf iteration 3): tokens are
    sorted by expert assignment and scattered into (E, C, d) buckets; each
    expert runs dense matmuls on its bucket only.  FLOPs drop from O(E) to
    O(top_k * capacity_factor) per token (~6.4x for 16e top-2).  With
    experts sharded over `model`, the scatter/gather pair is the
    all-to-all dispatch of standard EP.  Overflow beyond the static
    capacity is dropped (switch-style routing)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d)
    logits = (xf.astype(jnp.float32) @ p["router"])           # (T, E)
    topv, topi = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1).astype(x.dtype)     # (T, k)

    if T <= 512:
        # decode / tiny batches: full capacity (no drops) — the buckets are
        # small, and decode must be exact w.r.t. the teacher-forced path
        C = T
    else:
        C = max(1, int(T * k * capacity_factor) // E)
    expert_idx = topi.reshape(-1)                             # (T*k,)
    token_idx = jnp.repeat(jnp.arange(T), k)
    gate_flat = gates.reshape(-1)

    order = jnp.argsort(expert_idx)                           # stable
    se = expert_idx[order]
    stok = token_idx[order]
    sgate = gate_flat[order]
    counts = jnp.bincount(expert_idx, length=E)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k) - starts[se]                      # slot in expert
    keep = pos < C
    pos_c = jnp.where(keep, pos, 0)

    buckets = jnp.zeros((E, C, d), x.dtype)
    buckets = buckets.at[se, pos_c].add(
        jnp.where(keep[:, None], xf[stok], 0).astype(x.dtype))
    # EP: pin the bucket/expert axis to the model mesh axis — without this
    # GSPMD replicates the expert einsums on every device (§Perf M2)
    buckets = _maybe_constrain(buckets, ("model", None, None))

    ex = p["experts"]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buckets, ex["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buckets, ex["w_up"])
    h = _maybe_constrain(h, ("model", None, None))
    out = jnp.einsum("ecf,efd->ecd", h, ex["w_down"])         # (E, C, d)
    out = _maybe_constrain(out, ("model", None, None))

    y = jnp.zeros((T, d), x.dtype).at[stok].add(
        jnp.where(keep[:, None], out[se, pos_c]
                  * sgate[:, None].astype(x.dtype), 0))
    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, "swiglu")
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# Mamba (selective SSM)
# --------------------------------------------------------------------------

def init_mamba(key, cfg: ArchConfig):
    d = cfg.d_model
    di = cfg.mamba_expand * d
    ds = cfg.mamba_d_state
    dt_ = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 7)
    dt_rank = max(1, d // 16)
    return {
        "in_proj": _dense_init(ks[0], d, 2 * di, dt_),
        "conv_w": (jax.random.normal(ks[1], (cfg.mamba_conv, di),
                                     jnp.float32) * 0.1).astype(dt_),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": _dense_init(ks[2], di, dt_rank + 2 * ds, dt_),
        "dt_proj": _dense_init(ks[3], dt_rank, di, dt_),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32),
                                  (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _dense_init(ks[4], di, d, dt_),
    }


def _selective_scan(u, dt, A, B_, C, D):
    """u:(B,S,di) dt:(B,S,di) A:(di,ds) B_,C:(B,S,ds).  Associative scan
    over S (sub-quadratic; runs the long_500k shapes)."""
    dA = jnp.exp(dt[..., None] * A[None, None])               # (B,S,di,ds)
    dBu = dt[..., None] * B_[:, :, None, :] * u[..., None]    # (B,S,di,ds)

    def combine(a, b):
        (a1, b1), (a2, b2) = a, b
        return (a1 * a2, a2 * b1 + b2)

    _, h = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
    y = jnp.einsum("bsdn,bsn->bsd", h, C)
    return y + u * D[None, None]


def apply_mamba(p, x, cfg: ArchConfig, cache=None):
    """x: (B, S, d) -> (B, S, d).  cache (decode): conv window + ssm state."""
    B, S, d = x.shape
    di = cfg.mamba_expand * d
    ds = cfg.mamba_d_state
    dt_rank = max(1, d // 16)
    xz = x @ p["in_proj"]
    u, z = xz[..., :di], xz[..., di:]

    kconv = cfg.mamba_conv
    if cache is None:
        pad = jnp.pad(u, ((0, 0), (kconv - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * p["conv_w"][i][None, None]
                   for i in range(kconv))
        conv = jax.nn.silu(conv + p["conv_b"][None, None])
        new_cache = None
    else:
        win = jnp.concatenate([cache["conv"], u], axis=1)[:, -kconv:]
        conv = jnp.einsum("bkd,kd->bd", win.astype(jnp.float32),
                          p["conv_w"].astype(jnp.float32))[:, None]
        conv = jax.nn.silu(conv + p["conv_b"][None, None]).astype(x.dtype)
        new_cache = {"conv": win}

    proj = conv @ p["x_proj"]
    dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"]
                         + p["dt_bias"][None, None])
    B_ = proj[..., dt_rank:dt_rank + ds].astype(jnp.float32)
    C = proj[..., dt_rank + ds:].astype(jnp.float32)
    A = -jnp.exp(p["A_log"])

    if cache is None:
        y = _selective_scan(conv.astype(jnp.float32), dt.astype(jnp.float32),
                            A, B_, C, p["D"])
    else:
        dA = jnp.exp(dt[:, 0, :, None] * A[None])             # (B,di,ds)
        dBu = (dt[:, 0, :, None] * B_[:, 0, None, :]
               * conv[:, 0, :, None].astype(jnp.float32))
        h = cache["ssm"] * dA + dBu
        y = (jnp.einsum("bdn,bn->bd", h, C[:, 0])
             + conv[:, 0].astype(jnp.float32) * p["D"][None])[:, None]
        new_cache["ssm"] = h
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return y @ p["out_proj"], new_cache


def init_mamba_cache(cfg: ArchConfig, batch: int, dtype=None):
    dt = jnp.dtype(dtype or cfg.dtype)
    di = cfg.mamba_expand * cfg.d_model
    return {"conv": jnp.zeros((batch, cfg.mamba_conv, di), dt),
            "ssm": jnp.zeros((batch, di, cfg.mamba_d_state), jnp.float32)}


# --------------------------------------------------------------------------
# xLSTM blocks (mLSTM: matrix memory; sLSTM: scalar memory, exp gating)
# --------------------------------------------------------------------------

def init_mlstm(key, cfg: ArchConfig):
    d = cfg.d_model
    di = int(cfg.xlstm_proj_factor * d)
    nh = cfg.n_heads
    dh = di // nh
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    return {
        "up": _dense_init(ks[0], d, 2 * di, dt),
        "wq": _dense_init(ks[1], di, di, dt),
        "wk": _dense_init(ks[2], di, di, dt),
        "wv": _dense_init(ks[3], di, di, dt),
        "wif": _dense_init(ks[4], di, 2 * nh, jnp.float32, scale=0.02),
        "down": _dense_init(ks[5], di, d, dt),
    }


def apply_mlstm(p, x, cfg: ArchConfig, cache=None):
    """Chunkless parallel mLSTM (quadratic within sequence, linear state for
    decode).  For training we use the attention-like parallel form with
    cumulative gates; decode carries (C, n) matrix state."""
    B, S, d = x.shape
    di = int(cfg.xlstm_proj_factor * d)
    nh = cfg.n_heads
    dh = di // nh
    up = x @ p["up"]
    h_in, z = up[..., :di], up[..., di:]
    q = (h_in @ p["wq"]).reshape(B, S, nh, dh)
    k = (h_in @ p["wk"]).reshape(B, S, nh, dh) / math.sqrt(dh)
    v = (h_in @ p["wv"]).reshape(B, S, nh, dh)
    gates = h_in @ p["wif"]                                   # (B, S, 2nh)
    i_g = gates[..., :nh].astype(jnp.float32)                 # log-space in
    f_g = jax.nn.log_sigmoid(gates[..., nh:].astype(jnp.float32))

    if cache is None:
        # chunkwise-parallel form: O(S*C) memory instead of O(S^2) —
        # required for the 32k/500k shapes (DESIGN.md §4).
        y = _mlstm_chunkwise(q.astype(jnp.float32),
                             k.astype(jnp.float32),
                             v.astype(jnp.float32), i_g, f_g)
        new_cache = None
    else:
        # recurrent step: C <- f C + i v k^T ; n <- f n + i k
        i_t = jnp.exp(i_g[:, 0])                               # (B, nh)
        f_t = jnp.exp(f_g[:, 0])
        C = cache["C"] * f_t[..., None, None] + \
            i_t[..., None, None] * jnp.einsum(
                "bhd,bhe->bhde", v[:, 0].astype(jnp.float32),
                k[:, 0].astype(jnp.float32))
        n = cache["n"] * f_t[..., None] + i_t[..., None] \
            * k[:, 0].astype(jnp.float32)
        qf = q[:, 0].astype(jnp.float32)
        num = jnp.einsum("bhde,bhe->bhd", C, qf)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhe,bhe->bh", n, qf)), 1.0)
        y = (num / den[..., None])[:, None]
        new_cache = {"C": C, "n": n}
    y = y.reshape(B, -1, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    return y @ p["down"], new_cache


def init_mlstm_cache(cfg: ArchConfig, batch: int):
    di = int(cfg.xlstm_proj_factor * cfg.d_model)
    nh = cfg.n_heads
    dh = di // nh
    return {"C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, nh, dh), jnp.float32)}


def _mlstm_chunkwise(q, k, v, i_g, f_g, chunk: int = 128):
    """Chunkwise mLSTM (stabilized).  q,k,v: (B,S,nh,dh) f32; i_g raw input
    gate (log space), f_g log-sigmoid forget gate, both (B,S,nh).

    Within a chunk: attention-like parallel form with gate-decay matrix D;
    across chunks: matrix memory (C_mat, n, m) recurrence carried by a
    lax.scan.  Verified against the quadratic parallel form and the
    token-recurrent form in tests/models/test_xlstm_forms.py."""
    B, S, nh, dh = q.shape
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    nc = S // C

    def resh(x, extra=()):
        return x.reshape(B, nc, C, *x.shape[2:])

    qc, kc, vc = resh(q), resh(k), resh(v)                 # (B,nc,C,nh,dh)
    ic, fc = resh(i_g), resh(f_g)                          # (B,nc,C,nh)
    b = jnp.cumsum(fc, axis=2)                             # local cum decay
    g_total = b[:, :, -1]                                  # (B,nc,nh)

    # intra-chunk decay matrix: D[t,s] = b_t - b_s + i_s  (s <= t)
    logD = (b[:, :, :, None, :] - b[:, :, None, :, :]
            + ic[:, :, None, :, :])                        # (B,nc,C,C,nh)
    tri = jnp.tril(jnp.ones((C, C), bool))
    logD = jnp.where(tri[None, None, :, :, None], logD, -jnp.inf)
    m_intra = jnp.max(logD, axis=3)                        # (B,nc,C,nh)

    # per-chunk state-update exponents: g_total - b_s + i_s
    st_exp = g_total[:, :, None, :] - b + ic               # (B,nc,C,nh)
    m_state_upd = jnp.max(st_exp, axis=2)                  # (B,nc,nh)

    def scan_chunk(carry, xs):
        C_mat, n_vec, m_prev = carry                       # (B,nh,dh,dh) ...
        qk, kk, vk, bk, ik, gk, logDk, m_intrak, stk, mstk = xs
        # output stabilizer per position: max(inter, intra) exponents
        m_out = jnp.maximum(bk + m_prev[:, None], m_intrak)  # (B,C,nh)
        # inter-chunk contribution
        w_inter = jnp.exp(bk + m_prev[:, None] - m_out)      # (B,C,nh)
        y_inter = jnp.einsum("bhde,bche->bchd", C_mat, qk) \
            * w_inter[..., None]
        n_inter = jnp.einsum("bchd,bhd->bch", qk, n_vec) * w_inter
        # intra-chunk contribution
        Dk = jnp.exp(logDk - m_out[:, :, None, :])           # (B,C,C,nh)
        scores = jnp.einsum("bthd,bshd->btsh", qk, kk) * Dk
        y_intra = jnp.einsum("btsh,bshd->bthd", scores, vk)
        n_intra = jnp.sum(scores, axis=2)                    # (B,C,nh)
        denom = jnp.maximum(jnp.abs(n_inter + n_intra), jnp.exp(-m_out))
        y = (y_inter + y_intra) / denom[..., None]
        # state update
        m_new = jnp.maximum(m_prev + gk, mstk)               # (B,nh)
        decay = jnp.exp(m_prev + gk - m_new)
        w_upd = jnp.exp(stk - m_new[:, None])                # (B,C,nh)
        C_mat = C_mat * decay[..., None, None] + jnp.einsum(
            "bchd,bche->bhde", vk * w_upd[..., None], kk)
        n_vec = n_vec * decay[..., None] + jnp.einsum(
            "bchd,bch->bhd", kk, w_upd)
        return (C_mat, n_vec, m_new), y

    def tr(x):
        return jnp.moveaxis(x, 1, 0)

    carry0 = (jnp.zeros((B, nh, dh, dh), jnp.float32),
              jnp.zeros((B, nh, dh), jnp.float32),
              jnp.full((B, nh), -1e30, jnp.float32))
    xs = (tr(qc), tr(kc), tr(vc), tr(b), tr(ic), tr(g_total), tr(logD),
          tr(m_intra), tr(st_exp), tr(m_state_upd))
    _, ys = jax.lax.scan(scan_chunk, carry0, xs)
    return jnp.moveaxis(ys, 0, 1).reshape(B, S, nh, dh)


def init_slstm(key, cfg: ArchConfig):
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2)
    return {"w": _dense_init(ks[0], d, 4 * d, dt),
            "r": _dense_init(ks[1], d, 4 * d, dt),
            "b": jnp.zeros((4 * d,), jnp.float32)}


def apply_slstm(p, x, cfg: ArchConfig, cache=None):
    """sLSTM with exponential gating; sequential lax.scan over time."""
    B, S, d = x.shape
    wx = x @ p["w"]                                            # (B, S, 4d)

    def step(carry, wx_t):
        h, c, n, m = carry
        z = wx_t + h @ p["r"] + p["b"]
        zf = z.astype(jnp.float32)
        i_t, f_t, g_t, o_t = jnp.split(zf, 4, axis=-1)
        log_f = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(log_f + m, i_t)
        i_e = jnp.exp(i_t - m_new)
        f_e = jnp.exp(log_f + m - m_new)
        c_new = f_e * c + i_e * jnp.tanh(g_t)
        n_new = f_e * n + i_e
        h_new = (jax.nn.sigmoid(o_t) * c_new
                 / jnp.maximum(n_new, 1.0)).astype(x.dtype)
        return (h_new, c_new, n_new, m_new), h_new

    if cache is None:
        h0 = jnp.zeros((B, d), x.dtype)
        c0 = jnp.zeros((B, d), jnp.float32)
        n0 = jnp.zeros((B, d), jnp.float32)
        m0 = jnp.full((B, d), -1e30, jnp.float32)
        (_, _, _, _), ys = jax.lax.scan(step, (h0, c0, n0, m0),
                                        wx.transpose(1, 0, 2))
        return ys.transpose(1, 0, 2), None
    carry = (cache["h"], cache["c"], cache["n"], cache["m"])
    carry, y = step(carry, wx[:, 0])
    return y[:, None], {"h": carry[0], "c": carry[1], "n": carry[2],
                        "m": carry[3]}


def init_slstm_cache(cfg: ArchConfig, batch: int, dtype=None):
    d = cfg.d_model
    dt = jnp.dtype(dtype or cfg.dtype)
    return {"h": jnp.zeros((batch, d), dt),
            "c": jnp.zeros((batch, d), jnp.float32),
            "n": jnp.zeros((batch, d), jnp.float32),
            "m": jnp.full((batch, d), -1e30, jnp.float32)}


# --------------------------------------------------------------------------
# mHC hyper-connections (paper RQ3 as a first-class model feature)
# --------------------------------------------------------------------------

def init_mhc(key, cfg: ArchConfig):
    n = cfg.hyper_connections
    k1, k2, k3 = jax.random.split(key, 3)
    # symmetry breaking is essential: with identical streams, equal betas
    # and a uniform mixing matrix, the mHC parameters sit at a stationary
    # point (zero gradient) — streams would never diverge.
    return {"alpha": 0.02 * jax.random.normal(k1, (n,), jnp.float32),
            "logits": 0.02 * jax.random.normal(k2, (n, n), jnp.float32),
            "beta": (jnp.full((n,), 1.0 / n, jnp.float32)
                     + 0.02 * jax.random.normal(k3, (n,), jnp.float32))}


def sinkhorn(logits, iters: int):
    M = jnp.exp(logits)
    for _ in range(iters):
        M = M / M.sum(1, keepdims=True)
        M = M / M.sum(0, keepdims=True)
    return M


def mhc_pre(p, streams):
    """streams: (n, B, S, d) -> layer input (B, S, d)."""
    a = jax.nn.softmax(p["alpha"])
    return jnp.einsum("n,nbsd->bsd", a.astype(streams.dtype), streams)


def mhc_post(p, streams, layer_out, cfg: ArchConfig):
    """The mHC_post op (kernels/generated/mhc_post.py is its kernel).

    Under :func:`mhc_post_impl`'s ``"fused_bwd"`` scope (trace-time
    dispatch — ``make_train_step(fused_backward=True)`` activates it) the
    custom-VJP variant runs the EXTRACTED backward chain for the
    data-path cotangents (DESIGN.md §16)."""
    if _MHC_POST_IMPL[0] == "fused_bwd":
        return _mhc_post_fused(p, streams, layer_out, cfg.sinkhorn_iters)
    return _mhc_post_math(p, streams, layer_out, cfg.sinkhorn_iters)


def _mhc_post_math(p, streams, layer_out, iters: int):
    M = sinkhorn(p["logits"], iters).astype(streams.dtype)
    mixed = jnp.einsum("ij,jbsd->ibsd", M, streams)
    return mixed + p["beta"].astype(streams.dtype)[:, None, None, None] \
        * layer_out[None]


# trace-time mhc_post implementation switch (one-element list so the
# context manager mutates in place): "xla" | "fused_bwd"
_MHC_POST_IMPL = ["xla"]


class mhc_post_impl:
    """``with mhc_post_impl("fused_bwd"): ...`` — route every mhc_post
    traced in the scope through the custom-VJP variant whose backward is
    the extracted ``mhc_stream_bwd`` fusion chain."""

    def __init__(self, impl: str):
        if impl not in ("xla", "fused_bwd"):
            raise ValueError(f"unknown mhc_post impl {impl!r}")
        self.impl = impl

    def __enter__(self):
        self.prev = _MHC_POST_IMPL[0]
        _MHC_POST_IMPL[0] = self.impl
        return self

    def __exit__(self, *exc):
        _MHC_POST_IMPL[0] = self.prev
        return False


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _mhc_post_fused(p, streams, layer_out, iters):
    return _mhc_post_math(p, streams, layer_out, iters)


def _mhc_post_fused_fwd(p, streams, layer_out, iters):
    return (_mhc_post_math(p, streams, layer_out, iters),
            (p, streams, layer_out))


def _mhc_post_fused_bwd(iters, res, g):
    """Backward of mhc_post with the DATA-PATH cotangents (d_streams,
    d_layer_out) computed by the extracted mhc_stream_bwd chain
    (kernels/mhc_bwd.py) — the n+1 mixing trees run as ONE generated
    fused kernel per mix.  The tiny (n, n) parameter gradients (sinkhorn
    pullback, beta dot) stay XLA, mirroring the forward artifact's
    rationale (DESIGN.md §7, §16)."""
    from ..kernels.mhc_bwd import mhc_post_grad_derived
    p, streams, layer_out = res
    n, B, S, d = g.shape
    g32 = g.astype(jnp.float32)
    # (n, B, S, d) -> (B*S, n, d): the chain mixes streams per row
    g_rows = jnp.transpose(g32, (1, 2, 0, 3)).reshape(B * S, n, d)
    dh, do = mhc_post_grad_derived(g_rows, p["logits"], p["beta"],
                                   sinkhorn_iters=iters)
    d_streams = jnp.transpose(dh.reshape(B, S, n, d),
                              (2, 0, 1, 3)).astype(streams.dtype)
    d_layer_out = do.reshape(B, S, d).astype(layer_out.dtype)
    # parameter gradients: dM pulled back through sinkhorn, beta dot
    s32 = streams.astype(jnp.float32)
    dM = jnp.einsum("ibsd,jbsd->ij", g32, s32)
    _, sk_vjp = jax.vjp(lambda lg: sinkhorn(lg, iters), p["logits"])
    d_logits = sk_vjp(dM.astype(p["logits"].dtype))[0]
    d_beta = jnp.einsum("ibsd,bsd->i", g32,
                        layer_out.astype(jnp.float32)) \
        .astype(p["beta"].dtype)
    dp = {"alpha": jnp.zeros_like(p["alpha"]), "logits": d_logits,
          "beta": d_beta}
    return dp, d_streams, d_layer_out


_mhc_post_fused.defvjp(_mhc_post_fused_fwd, _mhc_post_fused_bwd)
