"""A configuration file, the program's model built from it, and weights made
by the benchmark from the seed.

The benchmark makes the weights itself, so that the plain reference
(``reference.py``) reads the same numbers without taking anything the
program made.  They are laid out as the program's dense transformer takes
them: ``embed``, ``lm_head``, ``final_norm`` and a ``body`` whose leaves
are stacked over layers.  Norm scales are drawn around 1 rather than set
to 1, so that a norm that drops its scale shows in the logits.
"""
from __future__ import annotations

import math

from .traffic import rng_for

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


def jax_key(seed: int):
    import jax
    return jax.random.PRNGKey(int(rng_for(seed, "weights").integers(2**31)))


def make_params(conf: dict, key):
    """Weights for ``conf`` from ``key``, in the served dtype.  Call under
    ``jax.jit`` so that they are made on the device in one program."""
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


def n_params(conf: dict) -> dict:
    """Parameter counts of the matrices that cost operations: the layers'
    and the LM head's (an embedding lookup costs none)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H, KV, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * f
    return {"layers": conf["num_hidden_layers"] * per_layer,
            "head": d * conf["vocab_size"]}
