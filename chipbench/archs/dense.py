"""The dense transformer block: attention with grouped query heads (or as
many key/value heads as query heads) and a SwiGLU MLP, in every layer.

An architecture module, named by a configuration file's ``bench_arch``,
gives the harness everything about a model that depends on its layers:

- ``arch_config(conf)``: the program's model for the file, checked width
  by width against it;
- ``make_params(conf, key)``: weights made by the benchmark from the seed,
  laid out as the program takes them;
- ``logits_at(params, conf, tokens, rows, quant=None)``: the plain float32
  reference and its lower-precision controls (``lib/reference.py``
  compares served tokens with it);
- ``n_params(conf)``, ``prefill_attention(conf, S)`` and
  ``decode_attention(conf, context)``: the work counts that
  ``lib/work.py`` adds up.

The reference is written from the published definition and imports
nothing of the program: RMSNorm with the file's epsilon, rotary embedding
on split halves, causal grouped-query attention with softmax scale
1/sqrt(head_dim), SwiGLU MLP, untied LM head.  Layers are scanned, each
cast to float32 only inside its own step, and attention runs one
key/value head at a time, so that the reference fits beside the weights
at the cell's sizes.  A control (``quant="int8"`` or ``"fp8"``) is the
same forward in the precision below the configuration's bfloat16: every
matmul's weights quantized per output column, its activations per row,
and keys and values per (position, head), each with a float32 scale.
"""
from __future__ import annotations

import math

from chipbench.lib.reference import matmul, quantize
from chipbench.lib.work import BF16, causal_pairs

# widths the program's config must match, file key -> ArchConfig field
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "vocab_size": "vocab", "num_hidden_layers": "n_layers",
          "rope_theta": "rope_theta", "kv_cache_dtype": "kv_cache_dtype"}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for this configuration file: the
    registered architecture, cut to the file's depth, checked width by
    width against the file."""
    from repro.configs import get_config
    cfg = get_config(conf["program_arch"],
                     smoke=conf.get("program_preset") == "smoke").scaled(
        n_layers=conf["num_hidden_layers"])
    for key, field in WIDTHS.items():
        got = getattr(cfg, field)
        if key == "head_dim":
            got = cfg.resolved_head_dim
        if got != conf[key]:
            raise ValueError(f"{conf['name']}: the program's {field}={got!r}"
                             f" differs from the file's {key}={conf[key]!r}")
    if cfg.dtype != conf["torch_dtype"] or cfg.tie_embeddings != \
            conf["tie_word_embeddings"] or cfg.prelude or cfg.mla or \
            cfg.n_experts or cfg.hyper_connections or cfg.qk_norm or \
            [(s.block, s.ffn) for s in cfg.pattern] != [("attn", "swiglu")]:
        raise ValueError(f"{conf['name']}: the program's layer is not the "
                         "dense attention + SwiGLU block this file states")
    return cfg


def make_params(conf: dict, key):
    """Weights for ``conf`` from ``key``, in the served dtype, laid out as
    the program's dense transformer takes them: ``embed``, ``lm_head``,
    ``final_norm`` and a ``body`` whose leaves are stacked over layers.
    Norm scales are drawn around 1 rather than set to 1, so that a norm
    that drops its scale shows in the logits.  Call under ``jax.jit`` so
    that they are made on the device in one program."""
    import jax
    import jax.numpy as jnp
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    V, L = conf["vocab_size"], conf["num_hidden_layers"]
    dt = jnp.dtype(conf["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def dense(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dt)

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    layer = {
        "norm1": {"scale": scale((L, d))},
        "block": {"wq": dense((L, d, H * hd)), "wk": dense((L, d, KV * hd)),
                  "wv": dense((L, d, KV * hd)), "wo": dense((L, H * hd, d))},
        "norm2": {"scale": scale((L, d))},
        "ffn": {"w_gate": dense((L, d, f)), "w_up": dense((L, d, f)),
                "w_down": dense((L, f, d))},
    }
    return {
        "embed": (jax.random.normal(next(ks), (V, d), jnp.float32)
                  * conf["initializer_range"]).astype(dt),
        "final_norm": {"scale": scale((d,))},
        "lm_head": dense((d, V)),
        "prelude": [],
        "body": {"l0": layer},
    }


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
        q = rope(matmul(h, a["wq"], quant).reshape(S, H, hd))
        k = rope(matmul(h, a["wk"], quant).reshape(S, KV, hd))
        v = matmul(h, a["wv"], quant).reshape(S, KV, hd)
        if quant:
            k, v = quantize(k, -1, quant), quantize(v, -1, quant)
        o = jax.lax.map(attend, (q.reshape(S, KV, group, hd)
                                 .transpose(1, 2, 0, 3),
                                 k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        o = o.transpose(2, 0, 1, 3).reshape(S, H * hd)    # (S, KV*group*hd)
        x = x + matmul(o, a["wo"], quant)
        h = rms(x, p["norm2"]["scale"])
        g = matmul(h, m["w_gate"], quant)
        u = matmul(h, m["w_up"], quant)
        return x + matmul(g * jax.nn.sigmoid(g) * u, m["w_down"], quant), \
            None

    x = params["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, params["body"]["l0"])
    return rms(x, params["final_norm"]["scale"].astype(f32))


def logits_at(params, conf, tokens, rows, quant=None):
    """Logits (R, vocab) at positions ``rows`` (R,) of ``tokens``."""
    import jax.numpy as jnp
    h = hidden(params, conf, tokens, quant)[rows]
    return matmul(h, params["lm_head"].astype(jnp.float32), quant)


def n_params(conf: dict) -> dict:
    """Parameter counts of the matrices that cost operations: the layers'
    and the LM head's (an embedding lookup costs none)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * f
    return {"layers": conf["num_hidden_layers"] * per_layer,
            "head": d * conf["vocab_size"]}


def prefill_attention(conf: dict, S: int) -> tuple[float, float]:
    """(operations, bytes) of causal attention over one S-token prompt,
    all layers: S(S+1)/2 query-key pairs per query head, each 2*hd
    operations for the score and 2*hd for the weighted value; Q and O
    per query head, K and V per key/value head, in bfloat16."""
    L, H, KV, hd = (conf["num_hidden_layers"], conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"])
    flops = L * H * 4 * hd * causal_pairs(S)
    nbytes = L * (2 * H + 2 * KV) * S * hd * BF16
    return float(flops), float(nbytes)


def decode_attention(conf: dict, context: int) -> int:
    """Operations of one decoded token's attention over ``context``
    positions (itself included), all layers."""
    L, H, hd = (conf["num_hidden_layers"], conf["num_attention_heads"],
                conf["head_dim"])
    return L * H * 4 * hd * context
