"""The work a layer needs, counted from shapes, whatever implements it.

Operations count a multiply-add as two.  Causal attention over S
positions needs S(S+1)/2 query-key pairs per head, each 2*hd operations
for the scores and 2*hd for the weighted values.  Bytes are what one pass
must move at the served precision (bfloat16): Q and O per query head, K
and V per key/value head.
"""
from __future__ import annotations

import json
from pathlib import Path

from .model import n_params

BF16 = 2


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown chip is an error."""
    table = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def causal_pairs(S: int) -> int:
    return S * (S + 1) // 2


def prefill_attention(conf: dict, S: int) -> tuple[float, float]:
    """(operations, bytes) of causal attention over one S-token prompt,
    all layers."""
    L, H, KV, hd = (conf["num_hidden_layers"], conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"])
    flops = L * H * 4 * hd * causal_pairs(S)
    nbytes = L * (2 * H + 2 * KV) * S * hd * BF16
    return float(flops), float(nbytes)


def prompt_flops(conf: dict, S: int) -> float:
    """Forward operations of an S-token prefill: every layer's matrices on
    every position, causal attention, and the LM head at the last
    position, which is the only one whose logits are needed."""
    n = n_params(conf)
    return float(2 * n["layers"] * S + prefill_attention(conf, S)[0]
                 + 2 * n["head"])


def decode_flops(conf: dict, context: int) -> float:
    """Forward operations of one decoded token attending ``context``
    positions (itself included)."""
    n = n_params(conf)
    L, H, hd = (conf["num_hidden_layers"], conf["num_attention_heads"],
                conf["head_dim"])
    return float(2 * n["layers"] + L * H * 4 * hd * context + 2 * n["head"])


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
