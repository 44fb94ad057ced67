"""The plain reference: a float32 forward of the dense GQA transformer,
written from its published definition and importing nothing of the
program.

RMSNorm with the configuration's published epsilon, rotary embedding on
split halves, causal grouped-query attention with softmax scale
1/sqrt(head_dim), SwiGLU MLP, untied LM head.  Matmuls run at ``highest``
precision.  Layers are scanned, each cast to float32 only inside its own
step, and attention runs one key/value head at a time, so that the
reference fits beside the weights at the cell's sizes.

A control (``quant="int8"`` or ``"fp8"``) is the same forward in the
precision below the configuration's bfloat16: every matmul's weights
quantized per output column, its activations per row, and keys and values
per (position, head), to int8 or to float8 (e4m3), each with a float32
scale.
"""
from __future__ import annotations

import math
from functools import lru_cache


def _quantize(x, axis, quant):
    """``x`` rounded to ``quant`` with a scale per slice along ``axis``."""
    import jax.numpy as jnp
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / top
    if quant == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(x, w, quant):
    if quant:
        x, w = _quantize(x, -1, quant), _quantize(w, 0, quant)
    return x @ w


def hidden(params, conf, tokens, quant=None):
    """Final-normed hidden states (S, d) for ``tokens`` (S,) int32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    group, eps = H // KV, conf["rms_norm_eps"]
    S = tokens.shape[0]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    inv = conf["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=f32) / hd)
    ang = jnp.arange(S, dtype=f32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):                                        # (S, heads, hd)
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(qkv):                  # one kv head: (group, S, hd), 2x (S, hd)
        q, k, v = qkv
        s = jnp.einsum("gqd,kd->gqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(s, -1), v)

    def layer(x, p):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        a, m = p["block"], p["ffn"]
        h = rms(x, p["norm1"]["scale"])
        q = rope(_matmul(h, a["wq"], quant).reshape(S, H, hd))
        k = rope(_matmul(h, a["wk"], quant).reshape(S, KV, hd))
        v = _matmul(h, a["wv"], quant).reshape(S, KV, hd)
        if quant:
            k, v = _quantize(k, -1, quant), _quantize(v, -1, quant)
        o = jax.lax.map(attend, (q.reshape(S, KV, group, hd)
                                 .transpose(1, 2, 0, 3),
                                 k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        o = o.transpose(2, 0, 1, 3).reshape(S, H * hd)    # (S, KV*group*hd)
        x = x + _matmul(o, a["wo"], quant)
        h = rms(x, p["norm2"]["scale"])
        g = _matmul(h, m["w_gate"], quant)
        u = _matmul(h, m["w_up"], quant)
        return x + _matmul(g * jax.nn.sigmoid(g) * u, m["w_down"], quant), \
            None

    x = params["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["body"]["l0"])
    return rms(x, params["final_norm"]["scale"].astype(f32))


def logits_at(params, conf, tokens, rows, quant=None):
    """Logits (R, vocab) at positions ``rows`` (R,) of ``tokens``."""
    import jax.numpy as jnp
    h = hidden(params, conf, tokens, quant)[rows]
    return _matmul(h, params["lm_head"].astype(jnp.float32), quant)


@lru_cache(maxsize=4)
def _compiled(conf_items, quant):
    import jax
    conf = dict(conf_items)
    return jax.jit(lambda p, t, r: logits_at(p, conf, t, r, quant))


def served_gaps(params, conf, prompt, served, pad_to, rows_to,
                controls=()):
    """For each served token, how far the reference's logit of it lies
    below the reference's best at that position; for each control in
    ``controls`` the same gap for the token the control puts first.

    ``prompt`` (S,) and ``served`` (n,) are host arrays; the sequence is
    padded to ``pad_to`` positions and the rows to ``rows_to``, so that
    one program serves every request of a cell.  Returns (gaps of the
    served tokens, {control: gaps of its tokens})."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    S, n = len(prompt), len(served)
    seq = np.zeros(pad_to, np.int32)
    seq[:S] = prompt
    seq[S:S + n - 1] = served[:-1]
    rows = np.full(rows_to, S - 1, np.int32)
    rows[:n] = np.arange(S - 1, S - 1 + n)
    conf_items = tuple(sorted((k, v) for k, v in conf.items()
                              if isinstance(v, (int, float, str))))
    args = (params, jnp.asarray(seq), jnp.asarray(rows))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(_compiled(conf_items, None)(*args))[:n]
        picks = {q: np.asarray(_compiled(conf_items, q)(*args))[:n]
                 .argmax(-1) for q in controls}
    best, at = ref.max(-1), np.arange(n)
    return best - ref[at, np.asarray(served)], \
        {q: best - ref[at, p] for q, p in picks.items()}
