"""DeepSeek-V2's block: multi-head latent attention (MLA) with YaRN rotary
scaling, and a mixture of routed and shared experts after a few leading
layers with a dense SwiGLU MLP.

The same interface as ``archs/dense.py`` (``arch_config``,
``make_params``, ``logits_at``, ``n_params``, ``prefill_attention``,
``decode_attention``), and ``prefill_moe``: the operations and bytes of
the expert layers in one prefill, and ``UNSCOPED``: the expert layers'
operations that a trace shows without their scope.

The reference is written from the published definition
(``modeling_deepseek.py`` of DeepSeek-V2-Lite) and imports nothing of the
program:

- attention: queries from ``wq`` (no query LoRA); a joint down-projection
  to a latent of ``kv_lora_rank`` values, RMS-normed, and one rotary key of
  ``qk_rope_head_dim`` shared by every head; the latent up-projected by
  ``wkv_b`` to each head's key part and value; rotary pairs interleaved,
  at YaRN frequencies; softmax scale 1/sqrt(nope + rope head dims) times
  ``mscale(factor, mscale_all_dim)**2``; causal over full keys and values,
  one head at a time;
- experts: float32 router logits, softmax over every routed expert, the
  greedy top ``num_experts_per_tok`` of it, renormalised only if
  ``norm_topk_prob``, times ``routed_scaling_factor``; each routed expert a
  SwiGLU MLP of ``moe_intermediate_size``, computed by scanning over the
  experts with each token's gate (zero off its top-k); the shared experts
  one SwiGLU MLP of ``n_shared_experts * moe_intermediate_size``;
- RMSNorm with the file's epsilon, untied LM head.

Layers and experts are scanned, each cast to float32 only inside its own
step, so that the reference fits beside the weights at the cell's sizes.
A control (``quant="int8"`` or ``"fp8"``) is the same forward in the
precision below the configuration's bfloat16: every matmul's weights
quantized per output column and its activations per row, and the keys and
values per (position, head), each with a float32 scale.
"""
from __future__ import annotations

import math

from chipbench.lib.reference import matmul, quantize
from chipbench.lib.work import BF16, causal_pairs

# widths the program's config must match, file key -> ArchConfig field
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "moe_intermediate_size": "d_ff_expert",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads",
          "kv_lora_rank": "kv_lora", "qk_nope_head_dim": "nope_head_dim",
          "qk_rope_head_dim": "rope_head_dim", "v_head_dim": "v_head_dim",
          "n_routed_experts": "n_experts", "num_experts_per_tok": "top_k",
          "n_shared_experts": "n_shared_experts", "vocab_size": "vocab",
          "num_hidden_layers": "n_layers", "rope_theta": "rope_theta",
          "norm_topk_prob": "norm_topk_prob"}
YARN = ("factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim")
# what the program computes and does not read from its config
FIXED = {"q_lora_rank": None, "scoring_func": "softmax",
         "topk_method": "greedy", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "routed_scaling_factor": 1,
         "rms_norm_eps": 1e-06, "hidden_act": "silu",
         "attention_bias": False}
# operations of a scope that the compiler emits without it, by a fragment
# of their name (``lib/layer_ops.py``): on a TPU the grouped expert
# products (``jax.lax.ragged_dot``) become Mosaic kernels ``ragged-dot-*``
UNSCOPED = {"moe": ("ragged-dot",)}


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for this configuration file: the
    registered architecture, cut to the file's depth, checked width by
    width against the file."""
    from repro.configs import get_config
    cfg = get_config(conf["program_arch"],
                     smoke=conf.get("program_preset") == "smoke").scaled(
        n_layers=conf["num_hidden_layers"])

    def differ(what, got, want):
        raise ValueError(f"{conf['name']}: the program's {what}={got!r} "
                         f"differs from the file's {want!r}")

    for key, field in WIDTHS.items():
        if getattr(cfg, field, None) != conf[key]:
            differ(field, getattr(cfg, field, None), conf[key])
    s = getattr(cfg, "rope_scaling", None)
    got = None if s is None else {k: getattr(s, k) for k in YARN}
    want = {k: conf["rope_scaling"][k] for k in YARN}
    if got != want or conf["rope_scaling"]["type"] != "yarn" or \
            want != {k: conf[f"rope_scaling.{k}"] for k in YARN}:
        differ("rope_scaling", got, conf["rope_scaling"])
    for key, value in FIXED.items():
        if conf[key] != value:
            differ(key, value, conf[key])
    dense = conf["first_k_dense_replace"]
    if cfg.dtype != conf["torch_dtype"] or cfg.tie_embeddings != \
            conf["tie_word_embeddings"] or not cfg.mla or \
            cfg.kv_cache_dtype != conf["kv_cache_dtype"] or \
            [(s.block, s.ffn) for s in cfg.prelude] != \
            [("attn", "swiglu")] * dense or \
            [(s.block, s.ffn) for s in cfg.pattern] != [("attn", "moe")]:
        raise ValueError(f"{conf['name']}: the program's layers are not the "
                         f"{dense} dense and then MLA + MoE layers, with "
                         "the cache, this file states")
    return cfg


def make_params(conf: dict, key):
    """Weights for ``conf`` from ``key``, in the served dtype, laid out as
    the program's transformer takes them: ``embed``, ``lm_head``,
    ``final_norm``, a ``prelude`` list of the leading dense layers and a
    ``body`` whose leaves are stacked over the expert layers.  Norm scales
    are drawn around 1 rather than set to 1, so that a norm that drops its
    scale shows in the logits.  Call under ``jax.jit`` so that they are
    made on the device in one program."""
    import jax
    import jax.numpy as jnp
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, c = conf["num_attention_heads"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    E, f = conf["n_routed_experts"], conf["moe_intermediate_size"]
    n_dense = conf["first_k_dense_replace"]
    L = conf["num_hidden_layers"] - n_dense
    dt = jnp.dtype(conf["torch_dtype"])
    ks = iter(jax.random.split(key, 32))

    def dense(shape):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(shape[-2])).astype(dt)

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)

    def mlp(lead, width):
        return {"w_gate": dense((*lead, d, width)),
                "w_up": dense((*lead, d, width)),
                "w_down": dense((*lead, width, d))}

    def layer(lead, ffn):
        return {
            "norm1": {"scale": scale((*lead, d))},
            "block": {"wq": dense((*lead, d, H * (dn + dr))),
                      "wkv_a": dense((*lead, d, c + dr)),
                      "kv_norm": scale((*lead, c)),
                      "wkv_b": dense((*lead, c, H * (dn + dv))),
                      "wo": dense((*lead, H * dv, d))},
            "norm2": {"scale": scale((*lead, d))},
            "ffn": ffn,
        }

    return {
        "embed": (jax.random.normal(next(ks), (V, d), jnp.float32)
                  * conf["initializer_range"]).astype(dt),
        "final_norm": {"scale": scale((d,))},
        "lm_head": dense((d, V)),
        "prelude": [layer((), mlp((), conf["intermediate_size"]))
                    for _ in range(n_dense)],
        "body": {"l0": layer((L,), {
            "router": dense((L, d, E)),
            "experts": mlp((L, E), f),
            "shared": mlp((L,), f * conf["n_shared_experts"])})},
    }


def _yarn(conf: dict) -> dict:
    """``rope_scaling``'s numbers, from the file's scalar copies of them
    (the reference is handed only a file's scalar keys)."""
    return {k: conf[f"rope_scaling.{k}"] for k in YARN}


def yarn_inv_freq(conf: dict) -> list[float]:
    """The rotary frequencies of ``DeepseekV2YarnRotaryEmbedding``."""
    dim, base = conf["qk_rope_head_dim"], conf["rope_theta"]
    s = _yarn(conf)

    def correction_dim(turns):
        return dim * math.log(s["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(s["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        extra = 1.0 / base ** (2 * i / dim)
        inter = extra / s["factor"]
        mask = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(inter * (1 - mask) + extra * mask)
    return out


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(conf: dict) -> float:
    s = _yarn(conf)
    m = yarn_mscale(s["factor"], s["mscale_all_dim"])
    return (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def hidden(params, conf, tokens, quant=None):
    """Final-normed hidden states (S, d) for ``tokens`` (S,) int32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    H, c = conf["num_attention_heads"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    k_top, eps = conf["num_experts_per_tok"], conf["rms_norm_eps"]
    E = conf["n_routed_experts"]
    S = tokens.shape[0]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    s = _yarn(conf)
    m = yarn_mscale(s["factor"], s["mscale"]) / yarn_mscale(
        s["factor"], s["mscale_all_dim"])
    ang = jnp.arange(S, dtype=f32)[:, None] * \
        jnp.asarray(yarn_inv_freq(conf), f32)[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None] * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None] * m

    def rope(x):                    # (S, heads, dr), pairs interleaved
        x = x.reshape(S, -1, dr // 2, 2).swapaxes(-1, -2).reshape(S, -1, dr)
        half = jnp.concatenate([-x[..., dr // 2:], x[..., :dr // 2]], -1)
        return x * cos + half * sin

    causal = jnp.tril(jnp.ones((S, S), bool))
    sm = softmax_scale(conf)

    def attend(qkv):                # one head: (S, dn + dr) x2, (S, dv)
        q, k, v = qkv
        a = jnp.where(causal, q @ k.T * sm, -jnp.inf)
        return jax.nn.softmax(a, -1) @ v

    def attention(x, a):
        h = x
        q = matmul(h, a["wq"], quant).reshape(S, H, dn + dr)
        kv_a = matmul(h, a["wkv_a"], quant)
        lat = rms(kv_a[:, :c], a["kv_norm"])
        k_pe = rope(kv_a[:, None, c:])                      # (S, 1, dr)
        kv = matmul(lat, a["wkv_b"], quant).reshape(S, H, dn + dv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_pe, (S, H, dr))], -1)
        v = kv[..., dn:]
        if quant:
            k, v = quantize(k, -1, quant), quantize(v, -1, quant)
        o = jax.lax.map(attend, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))       # (H, S, dv)
        return matmul(o.transpose(1, 0, 2).reshape(S, H * dv), a["wo"],
                      quant)

    def mlp(h, m_):
        g = matmul(h, m_["w_gate"].astype(f32), quant)
        u = matmul(h, m_["w_up"].astype(f32), quant)
        return matmul(g * jax.nn.sigmoid(g) * u, m_["w_down"].astype(f32),
                      quant)

    def experts(h, p):
        probs = jax.nn.softmax(matmul(h, p["router"].astype(f32), quant), -1)
        top, idx = jax.lax.top_k(probs, k_top)
        if conf["norm_topk_prob"]:
            top = top / top.sum(-1, keepdims=True)
        top = top * conf["routed_scaling_factor"]
        gate = jnp.einsum("ske,sk->se", jax.nn.one_hot(idx, E, dtype=f32),
                          top)                                # (S, E)

        def one(y, e):
            w, g = e
            return y + g[:, None] * mlp(h, w), None
        y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                            (p["experts"], gate.T))
        return y + mlp(h, p["shared"])

    def layer(x, p, ffn):
        small = {k: v for k, v in p.items() if k != "ffn"}
        small = jax.tree.map(lambda a: a.astype(f32), small)
        x = x + attention(rms(x, small["norm1"]["scale"]), small["block"])
        return x + ffn(rms(x, small["norm2"]["scale"]), p["ffn"])

    x = params["embed"][tokens].astype(f32)
    for p in params["prelude"]:
        x = layer(x, p, mlp)
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p, experts), None), x,
                        params["body"]["l0"])
    return rms(x, params["final_norm"]["scale"].astype(f32))


def logits_at(params, conf, tokens, rows, quant=None):
    """Logits (R, vocab) at positions ``rows`` (R,) of ``tokens``."""
    import jax.numpy as jnp
    h = hidden(params, conf, tokens, quant)[rows]
    return matmul(h, params["lm_head"].astype(jnp.float32), quant)


def _attention_params(conf: dict) -> int:
    d, H, c = (conf["hidden_size"], conf["num_attention_heads"],
               conf["kv_lora_rank"])
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    return d * H * (dn + dr) + d * (c + dr) + c * H * (dn + dv) + H * dv * d


def _moe_layers(conf: dict) -> int:
    return conf["num_hidden_layers"] - conf["first_k_dense_replace"]


def n_params(conf: dict) -> dict:
    """Parameter counts of the matrices that cost operations per token:
    the layers' (attention with the latent's up-projection, the dense
    layers' MLP, and in the expert layers the router, the shared experts
    and the ``num_experts_per_tok`` routed experts a token goes through)
    and the LM head's (an embedding lookup costs none)."""
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    active = d * conf["n_routed_experts"] + 3 * d * f * (
        conf["num_experts_per_tok"] + conf["n_shared_experts"])
    layers = conf["num_hidden_layers"] * _attention_params(conf) \
        + conf["first_k_dense_replace"] * 3 * d * conf["intermediate_size"] \
        + _moe_layers(conf) * active
    return {"layers": layers, "head": d * conf["vocab_size"]}


def _head_work(conf: dict) -> tuple[int, int]:
    """Operations per query-key pair and head (score and weighted value,
    in the published form), and values of Q, K, V and O per position and
    head."""
    qk = conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
    dv = conf["v_head_dim"]
    return 2 * qk + 2 * dv, 2 * qk + 2 * dv


def prefill_attention(conf: dict, S: int) -> tuple[float, float]:
    """(operations, bytes) of causal attention over one S-token prompt,
    all layers, in the published form: S(S+1)/2 query-key pairs per head,
    each 2*(nope + rope) operations for the score and 2*v for the weighted
    value; Q, K, V and O per head, in bfloat16."""
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    ops, values = _head_work(conf)
    return float(L * H * ops * causal_pairs(S)), \
        float(L * H * values * S * BF16)


def decode_attention(conf: dict, context: int) -> int:
    """Operations of one decoded token's attention over ``context``
    positions (itself included), all layers, in the published form."""
    return conf["num_hidden_layers"] * conf["num_attention_heads"] * \
        _head_work(conf)[0] * context


def prefill_moe(conf: dict, S: int) -> tuple[float, float]:
    """(operations, bytes) of the expert layers over one S-token prompt:
    the router and S * (top-k + shared) expert MLPs per layer; the
    weights of every routed expert (a prompt of 512 tokens or more routes
    to all of them), the shared experts and the router, and the
    activations in and out, in bfloat16."""
    d, f, E = (conf["hidden_size"], conf["moe_intermediate_size"],
               conf["n_routed_experts"])
    k, shared = conf["num_experts_per_tok"], conf["n_shared_experts"]
    ops = 2 * S * (d * E + 3 * d * f * (k + shared))
    weights = d * E + 3 * d * f * (E + shared)
    L = _moe_layers(conf)
    return float(L * ops), float(L * (weights + 2 * S * d) * BF16)
