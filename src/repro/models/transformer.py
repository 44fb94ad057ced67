"""Unified model implementation for all 10 assigned architectures.

The model is a prelude (unrolled layers) + a scanned body: the body repeats
``cfg.pattern`` (a period of LayerSpecs) ``cfg.repeats`` times with
period-stacked parameters, giving O(period) HLO size for deep stacks —
essential for compiling 64-layer configs against a 512-device mesh.

Entry points:
  init_params(key, cfg)                         -> pytree
  forward(params, cfg, batch)                   -> logits      (train/encode)
  loss_fn(params, cfg, batch)                   -> scalar loss
  prefill(params, cfg, batch, max_len)          -> (logits, caches)
  decode_step(params, cfg, tokens, caches)      -> (logits, caches)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ArchConfig, LayerSpec


# --------------------------------------------------------------------------
# per-layer init / apply
# --------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, spec: LayerSpec):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.init_norm(cfg)}
    if spec.block == "attn":
        p["block"] = (L.init_mla(k1, cfg) if cfg.mla
                      else L.init_attention(k1, cfg))
    elif spec.block == "mamba":
        p["block"] = L.init_mamba(k1, cfg)
    elif spec.block == "mlstm":
        p["block"] = L.init_mlstm(k1, cfg)
    elif spec.block == "slstm":
        p["block"] = L.init_slstm(k1, cfg)
    else:
        raise ValueError(spec.block)
    if spec.ffn != "none":
        p["norm2"] = L.init_norm(cfg)
        if spec.ffn == "moe":
            p["ffn"] = L.init_moe(k2, cfg)
        else:
            p["ffn"] = L.init_mlp(k2, cfg, spec.ffn)
    if cfg.hyper_connections:
        p["mhc_block"] = L.init_mhc(k3, cfg)
        if spec.ffn != "none":
            p["mhc_ffn"] = L.init_mhc(k4, cfg)
    return p


def _apply_block(p, spec: LayerSpec, x, cfg: ArchConfig, positions, cache):
    if spec.block == "attn":
        if cfg.mla:
            return L.apply_mla(p, x, cfg, positions=positions, cache=cache)
        return L.apply_attention(p, x, cfg, positions=positions, cache=cache)
    if spec.block == "mamba":
        return L.apply_mamba(p, x, cfg, cache=cache)
    if spec.block == "mlstm":
        return L.apply_mlstm(p, x, cfg, cache=cache)
    if spec.block == "slstm":
        return L.apply_slstm(p, x, cfg, cache=cache)
    raise ValueError(spec.block)


# Named scopes put each op's layer into its HLO metadata (``op_name``), so a
# profiler trace splits a step's device time by layer.  They change no jaxpr.

def _block_scope(spec: LayerSpec) -> str:
    return "attention" if spec.block == "attn" else spec.block


def _norm(p, x, cfg: ArchConfig):
    with jax.named_scope("norm"):
        return L.apply_norm(p, x, cfg)


def _apply_ffn(p, spec: LayerSpec, h, cfg: ArchConfig):
    if spec.ffn == "moe":
        with jax.named_scope("moe"):
            return L.apply_moe(p, h, cfg)
    with jax.named_scope("mlp"):
        return L.apply_mlp(p, h, spec.ffn)


def _mhc_pre(p, streams):
    with jax.named_scope("mhc"):
        return L.mhc_pre(p, streams)


def _mhc_post(p, streams, out, cfg: ArchConfig):
    with jax.named_scope("mhc"):
        return L.mhc_post(p, streams, out, cfg)


def _lm_head(params, cfg: ArchConfig, state):
    """The final norm and the output projection."""
    with jax.named_scope("lm_head"):
        h = L.apply_norm(params["final_norm"], state, cfg)
        if cfg.encoder_only:
            return h @ params["head"]
        if cfg.tie_embeddings:
            return h @ params["embed"].T
        return h @ params["lm_head"]


def _apply_layer(p, spec: LayerSpec, state, cfg: ArchConfig, positions,
                 cache):
    """state: x (B,S,d) or streams (n,B,S,d) when hyper-connections on."""
    if cfg.hyper_connections:
        streams = state
        inp = _mhc_pre(p["mhc_block"], streams)
        h = _norm(p["norm1"], inp, cfg)
        with jax.named_scope(_block_scope(spec)):
            out, new_cache = _apply_block(p["block"], spec, h, cfg,
                                          positions, cache)
        streams = _mhc_post(p["mhc_block"], streams, out, cfg)
        if spec.ffn != "none":
            inp = _mhc_pre(p["mhc_ffn"], streams)
            out = _apply_ffn(p["ffn"], spec, _norm(p["norm2"], inp, cfg),
                             cfg)
            streams = _mhc_post(p["mhc_ffn"], streams, out, cfg)
        return streams, new_cache

    x = state
    h = _norm(p["norm1"], x, cfg)
    with jax.named_scope(_block_scope(spec)):
        out, new_cache = _apply_block(p["block"], spec, h, cfg, positions,
                                      cache)
    x = x + out
    if spec.ffn != "none":
        x = x + _apply_ffn(p["ffn"], spec, _norm(p["norm2"], x, cfg), cfg)
    return x, new_cache


# --------------------------------------------------------------------------
# model init
# --------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig):
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    p: Dict[str, Any] = {}
    p["embed"] = (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt)
    p["final_norm"] = L.init_norm(cfg)
    if cfg.encoder_only:
        p["head"] = L._dense_init(keys[1], cfg.d_model, cfg.vocab, dt)
    elif not cfg.tie_embeddings:
        p["lm_head"] = L._dense_init(keys[1], cfg.d_model, cfg.vocab, dt)

    p["prelude"] = [
        _init_layer(jax.random.fold_in(keys[2], i), cfg, spec)
        for i, spec in enumerate(cfg.prelude)
    ]

    def init_period(k):
        ks = jax.random.split(k, len(cfg.pattern))
        return {f"l{i}": _init_layer(ks[i], cfg, spec)
                for i, spec in enumerate(cfg.pattern)}

    period_keys = jax.random.split(keys[3], cfg.repeats)
    p["body"] = jax.vmap(init_period)(period_keys)   # leaves: (repeats, ...)
    return p


# --------------------------------------------------------------------------
# forward / loss (train & encode)
# --------------------------------------------------------------------------

def _embed_inputs(params, cfg: ArchConfig, batch):
    """Returns x (B, S, d).  Modality frontends are stubs: precomputed
    frame/patch embeddings arrive in the batch (DESIGN.md §4)."""
    if cfg.frontend == "audio":
        return batch["frames"].astype(jnp.dtype(cfg.dtype))
    with jax.named_scope("embed"):
        tok = params["embed"][batch["tokens"]]
        if cfg.frontend == "patch":
            return jnp.concatenate(
                [batch["patch_embeds"].astype(tok.dtype), tok], axis=1)
        return tok


def _body_scan(params, cfg: ArchConfig, state, positions, caches=None):
    """Scan the period-stacked body.  caches: None or per-period stacked
    pytrees; returns (state, new_caches)."""
    specs = cfg.pattern

    def one_period(state, xs):
        layer_params, cache_in = xs
        new_caches = {}
        for i, spec in enumerate(specs):
            c = None if cache_in is None else cache_in.get(f"l{i}")
            state, nc = _apply_layer(layer_params[f"l{i}"], spec, state, cfg,
                                     positions, c)
            new_caches[f"l{i}"] = nc
        if all(v is None for v in new_caches.values()):
            new_caches = None
        return state, new_caches

    body = one_period
    if cfg.remat == "full":
        body = jax.checkpoint(one_period,
                              prevent_cse=False)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            one_period, prevent_cse=False,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    def scan_fn(carry, xs):
        out, ncache = body(carry, xs)
        # pin the scan carry's layout so the per-layer stacked buffer (and
        # the decode-cache dynamic_update_slice) keeps ONE sharding across
        # iterations instead of remat-resharding at the loop boundary
        # (no-op outside a mesh context)
        from ..distributed.sharding import constrain_activation
        batch_axis = 1 if out.ndim == 4 else 0   # hyper-connection streams
        out = constrain_activation(out, batch_axis=batch_axis)
        return out, ncache

    xs = (params["body"], caches)
    state, new_caches = jax.lax.scan(scan_fn, state, xs)
    return state, new_caches


def forward(params, cfg: ArchConfig, batch, caches=None):
    """Full-sequence forward.  Returns (logits, new_caches)."""
    x = _embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    positions = jnp.arange(S)
    state = x
    if cfg.hyper_connections:
        state = jnp.broadcast_to(x[None],
                                 (cfg.hyper_connections, *x.shape))
    prelude_caches = None if caches is None else caches["prelude"]
    new_prelude = []
    for i, spec in enumerate(cfg.prelude):
        c = None if prelude_caches is None else prelude_caches[i]
        state, nc = _apply_layer(params["prelude"][i], spec, state, cfg,
                                 positions, c)
        new_prelude.append(nc)
    body_caches = None if caches is None else caches["body"]
    state, new_body = _body_scan(params, cfg, state, positions, body_caches)
    if cfg.hyper_connections:
        state = state.sum(0)
    logits = _lm_head(params, cfg, state)
    new_caches = None
    if caches is not None:
        new_caches = {"prelude": new_prelude, "body": new_body}
    return logits, new_caches


def loss_fn(params, cfg: ArchConfig, batch):
    """Next-token CE for causal LMs; frame classification for encoders.
    ``batch['loss_mask']`` (optional) masks positions (frontend prefixes)."""
    logits, _ = forward(params, cfg, batch)
    if cfg.encoder_only:
        labels = batch["labels"]
        lg = logits
    else:
        tokens = batch["tokens"]
        text_len = tokens.shape[1]
        lg = logits[:, -text_len:-1]           # predict next text token
        labels = tokens[:, 1:]
    # multi-pod SPMD: keep the vocab axis model-sharded through the loss.
    # A take_along_axis gather over a sharded vocab axis makes XLA
    # replicate the full f32 logits (tens of GB of temps); the label
    # pick as an equality-mask sum partitions cleanly instead.
    from ..distributed.sharding import constrain_activation
    lg = constrain_activation(lg.astype(jnp.float32))
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    vocab_iota = jnp.arange(lg.shape[-1], dtype=labels.dtype)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], lg, 0.0),
                   axis=-1)
    nll = logz - gold
    mask = batch.get("loss_mask")
    if mask is not None:
        m = mask[:, -nll.shape[1]:].astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
    return nll.mean()


# --------------------------------------------------------------------------
# serving: prefill + single-token decode
# --------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, max_len: int):
    def cache_for(spec: LayerSpec):
        if spec.block == "attn":
            return (L.init_mla_cache(cfg, batch, max_len) if cfg.mla
                    else L.init_attention_cache(cfg, batch, max_len))
        if spec.block == "mamba":
            return L.init_mamba_cache(cfg, batch)
        if spec.block == "mlstm":
            return L.init_mlstm_cache(cfg, batch)
        if spec.block == "slstm":
            return L.init_slstm_cache(cfg, batch)
        raise ValueError(spec.block)

    prelude = [cache_for(s) for s in cfg.prelude]

    if cfg.serve_unroll_layers:
        # per-layer cache arrays (no stacking): static slicing in decode,
        # shardings preserved — no involuntary remat (§Perf iteration 1)
        body = [{f"l{i}": cache_for(s) for i, s in enumerate(cfg.pattern)}
                for _ in range(cfg.repeats)]
        return {"prelude": prelude, "body_layers": body}

    def stack(c):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.repeats, *jnp.shape(a)))
            if not isinstance(a, int) else a, c)

    body = {f"l{i}": stack(cache_for(s)) for i, s in enumerate(cfg.pattern)}
    return {"prelude": prelude, "body": body}


def _unrolled_layer_params(params, cfg: ArchConfig, rep: int):
    return {f"l{i}": jax.tree.map(lambda a: a[rep], params["body"][f"l{i}"])
            for i in range(len(cfg.pattern))}


def decode_step(params, cfg: ArchConfig, tokens, caches):
    """tokens: (B, 1) int32 -> (logits (B, 1, V), new caches)."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    # positions for rope come from per-layer cache lengths; use the first
    # attention cache's length (all layers advance in lockstep)
    pos = _first_length(caches, cfg)
    B = tokens.shape[0]
    positions = pos[:, None] if pos is not None else jnp.zeros((B, 1),
                                                               jnp.int32)
    state = x
    if cfg.hyper_connections:
        state = jnp.broadcast_to(x[None],
                                 (cfg.hyper_connections, *x.shape))
    new_prelude = []
    for i, spec in enumerate(cfg.prelude):
        state, nc = _apply_layer(params["prelude"][i], spec, state, cfg,
                                 positions, caches["prelude"][i])
        new_prelude.append(nc)

    if "body_layers" in caches:       # unrolled decode (§Perf iteration 1)
        new_body = []
        for rep in range(cfg.repeats):
            lp = _unrolled_layer_params(params, cfg, rep)
            ncs = {}
            for i, spec in enumerate(cfg.pattern):
                state, nc = _apply_layer(lp[f"l{i}"], spec, state, cfg,
                                         positions,
                                         caches["body_layers"][rep][f"l{i}"])
                ncs[f"l{i}"] = nc
            new_body.append(ncs)
        body_key, body_val = "body_layers", new_body
    else:
        state, new_body = _body_scan(params, cfg, state, positions,
                                     caches["body"])
        body_key, body_val = "body", new_body
    if cfg.hyper_connections:
        state = state.sum(0)
    logits = _lm_head(params, cfg, state)
    return logits, {"prelude": new_prelude, body_key: body_val}


def _first_length(caches, cfg: ArchConfig):
    for i, spec in enumerate(cfg.prelude):
        if spec.block == "attn":
            return caches["prelude"][i]["length"]
    for i, spec in enumerate(cfg.pattern):
        if spec.block == "attn":
            if "body_layers" in caches:
                return caches["body_layers"][0][f"l{i}"]["length"]
            return caches["body"][f"l{i}"]["length"][0]
    return None


def prefill(params, cfg: ArchConfig, batch, max_len: int):
    """Encode a prompt and build decode caches.  For simplicity and
    compile-size economy this runs token-parallel attention over the prompt
    (flash path) and then *bulk-writes* the caches."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    caches = init_caches(cfg, B, max_len)
    logits, new_caches = _prefill_forward(params, cfg, batch, caches)
    return logits, new_caches


def _prefill_forward(params, cfg, batch, caches):
    """Prefill: run the parallel forward while populating caches via the
    per-layer cache protocols (each block writes its full-sequence state)."""
    x = _embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    positions = jnp.arange(S)
    state = x

    def fill_layer(p, spec, state, cache):
        # run parallel block; then write sequence K/V (attn) or final state
        # (recurrent blocks) into the cache.
        if cfg.hyper_connections:
            inp = _mhc_pre(p["mhc_block"], state)
        else:
            inp = state
        h = _norm(p["norm1"], inp, cfg)
        with jax.named_scope(_block_scope(spec)):
            if spec.block == "attn":
                new_cache = _fill_attn_cache(p["block"], h, cfg, cache,
                                             positions)
            else:
                new_cache = _fill_recurrent_cache(p["block"], spec, h, cfg,
                                                  cache)
            out, _ = _apply_block(p["block"], spec, h, cfg, positions, None)
        if cfg.hyper_connections:
            state = _mhc_post(p["mhc_block"], state, out, cfg)
            if spec.ffn != "none":
                inp2 = _mhc_pre(p["mhc_ffn"], state)
                out2 = _apply_ffn(p["ffn"], spec,
                                  _norm(p["norm2"], inp2, cfg), cfg)
                state = _mhc_post(p["mhc_ffn"], state, out2, cfg)
        else:
            state = state + out
            if spec.ffn != "none":
                state = state + _apply_ffn(
                    p["ffn"], spec, _norm(p["norm2"], state, cfg), cfg)
        return state, new_cache

    new_prelude = []
    for i, spec in enumerate(cfg.prelude):
        state, nc = fill_layer(params["prelude"][i], spec, state,
                               caches["prelude"][i])
        new_prelude.append(nc)

    if "body_layers" in caches:
        new_body = []
        for rep in range(cfg.repeats):
            lp = _unrolled_layer_params(params, cfg, rep)
            ncs = {}
            for i, spec in enumerate(cfg.pattern):
                state, nc = fill_layer(lp[f"l{i}"], spec, state,
                                       caches["body_layers"][rep][f"l{i}"])
                ncs[f"l{i}"] = nc
            new_body.append(ncs)
        body_key, body_val = "body_layers", new_body
    else:
        def scan_fn(carry, xs):
            layer_params, cache_in = xs
            st = carry
            ncs = {}
            for i, spec in enumerate(cfg.pattern):
                st, nc = fill_layer(layer_params[f"l{i}"], spec, st,
                                    cache_in[f"l{i}"])
                ncs[f"l{i}"] = nc
            return st, ncs

        state, body_val = jax.lax.scan(scan_fn, state,
                                       (params["body"], caches["body"]))
        body_key = "body"
    if cfg.hyper_connections:
        state = state.sum(0)
    logits = _lm_head(params, cfg, state)
    return logits, {"prelude": new_prelude, body_key: body_val}


def _fill_attn_cache(p, h, cfg: ArchConfig, cache, positions):
    B, S = h.shape[:2]
    if cfg.mla:
        kv_a = h @ p["wkv_a"]
        c_kv, k_pe = kv_a[..., :cfg.kv_lora], kv_a[..., cfg.kv_lora:]
        c_kv = (c_kv.astype(jnp.float32)
                * jax.lax.rsqrt((c_kv.astype(jnp.float32) ** 2)
                                .mean(-1, keepdims=True) + 1e-6)
                * p["kv_norm"]).astype(h.dtype)
        cos, sin = L.rope_freqs(cfg.rope_head_dim, cfg.rope_theta, positions)
        k_pe = L.apply_rope(k_pe[:, :, None, :], cos, sin)[:, :, 0, :]
        new = dict(cache)
        new["c_kv"] = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv, (0, 0, 0))
        new["k_pe"] = jax.lax.dynamic_update_slice(
            cache["k_pe"], k_pe, (0, 0, 0))
        new["length"] = jnp.full_like(cache["length"], S)
        return new
    hd = cfg.resolved_head_dim
    k = (h @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (h @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = L._qk_norm(k, p["k_norm"])
    cos, sin = L.rope_freqs(hd, cfg.rope_theta, positions)
    k = L.apply_rope(k, cos, sin)
    new = dict(cache)
    if cfg.kv_cache_dtype == "int8":
        kq, ks = L._q8(k)
        vq, vs = L._q8(v)
        new["k"] = jax.lax.dynamic_update_slice(cache["k"], kq,
                                                (0, 0, 0, 0))
        new["v"] = jax.lax.dynamic_update_slice(cache["v"], vq,
                                                (0, 0, 0, 0))
        new["k_scale"] = jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                                      (0, 0, 0))
        new["v_scale"] = jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                                      (0, 0, 0))
    else:
        new["k"] = jax.lax.dynamic_update_slice(cache["k"], k.astype(
            cache["k"].dtype), (0, 0, 0, 0))
        new["v"] = jax.lax.dynamic_update_slice(cache["v"], v.astype(
            cache["v"].dtype), (0, 0, 0, 0))
    new["length"] = jnp.full_like(cache["length"], S)
    return new


def _fill_recurrent_cache(p, spec, h, cfg: ArchConfig, cache):
    """Populate recurrent state by running the block's parallel form and
    extracting the final state.  For compile-economy we recompute the final
    state with a short scan over the last `conv` window (mamba) or keep the
    mathematical final state (mlstm/slstm) via their scan outputs."""
    B, S = h.shape[:2]
    if spec.block == "mamba":
        di = cfg.mamba_expand * cfg.d_model
        xz = h @ p["in_proj"]
        u = xz[..., :di]
        new = dict(cache)
        win = jnp.zeros_like(cache["conv"])
        take = min(cfg.mamba_conv, S)
        win = jax.lax.dynamic_update_slice(
            win, u[:, -take:].astype(win.dtype),
            (0, cfg.mamba_conv - take, 0))
        new["conv"] = win
        # final ssm state: run the scan and keep h_T
        kconv = cfg.mamba_conv
        pad = jnp.pad(u, ((0, 0), (kconv - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * p["conv_w"][i][None, None]
                   for i in range(kconv))
        conv = jax.nn.silu(conv + p["conv_b"][None, None])
        dt_rank = max(1, cfg.d_model // 16)
        proj = conv @ p["x_proj"]
        dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"]
                             + p["dt_bias"][None, None]).astype(jnp.float32)
        ds = cfg.mamba_d_state
        B_ = proj[..., dt_rank:dt_rank + ds].astype(jnp.float32)
        A = -jnp.exp(p["A_log"])
        dA = jnp.exp(dt[..., None] * A[None, None])
        dBu = dt[..., None] * B_[:, :, None, :] \
            * conv.astype(jnp.float32)[..., None]

        def combine(a, b):
            (a1, b1), (a2, b2) = a, b
            return (a1 * a2, a2 * b1 + b2)
        _, hs = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
        new["ssm"] = hs[:, -1]
        return new
    if spec.block == "mlstm":
        # final C, n via the recurrence in log-gate space (scan)
        di = int(cfg.xlstm_proj_factor * cfg.d_model)
        nh = cfg.n_heads
        dh = di // nh
        up = h @ p["up"]
        h_in = up[..., :di]
        k = (h_in @ p["wk"]).reshape(B, S, nh, dh) / math.sqrt(dh)
        v = (h_in @ p["wv"]).reshape(B, S, nh, dh)
        gates = h_in @ p["wif"]
        i_g = gates[..., :nh].astype(jnp.float32)
        f_g = jax.nn.log_sigmoid(gates[..., nh:].astype(jnp.float32))

        def step(carry, xs):
            C, n = carry
            kt, vt, it, ft = xs
            i_t, f_t = jnp.exp(it), jnp.exp(ft)
            C = C * f_t[..., None, None] + i_t[..., None, None] * \
                jnp.einsum("bhd,bhe->bhde", vt.astype(jnp.float32),
                           kt.astype(jnp.float32))
            n = n * f_t[..., None] + i_t[..., None] * kt.astype(jnp.float32)
            return (C, n), None
        (C, n), _ = jax.lax.scan(
            step, (cache["C"], cache["n"]),
            (k.transpose(1, 0, 2, 3), v.transpose(1, 0, 2, 3),
             i_g.transpose(1, 0, 2), f_g.transpose(1, 0, 2)))
        return {"C": C, "n": n}
    if spec.block == "slstm":
        out, _ = L.apply_slstm(p, h, cfg, cache=None)
        # re-run statefully over the last step only is incorrect; run scan
        # with explicit carry capture:
        wx = h @ p["w"]

        def step(carry, wx_t):
            hh, c, n, m = carry
            z = wx_t + hh @ p["r"] + p["b"]
            zf = z.astype(jnp.float32)
            i_t, f_t, g_t, o_t = jnp.split(zf, 4, axis=-1)
            log_f = jax.nn.log_sigmoid(f_t)
            m_new = jnp.maximum(log_f + m, i_t)
            i_e = jnp.exp(i_t - m_new)
            f_e = jnp.exp(log_f + m - m_new)
            c_new = f_e * c + i_e * jnp.tanh(g_t)
            n_new = f_e * n + i_e
            h_new = (jax.nn.sigmoid(o_t) * c_new
                     / jnp.maximum(n_new, 1.0)).astype(h.dtype)
            return (h_new, c_new, n_new, m_new), None
        carry, _ = jax.lax.scan(
            step, (cache["h"], cache["c"], cache["n"], cache["m"]),
            wx.transpose(1, 0, 2))
        return {"h": carry[0], "c": carry[1], "n": carry[2], "m": carry[3]}
    raise ValueError(spec.block)
