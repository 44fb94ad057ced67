"""The work a step needs, counted from shapes, whatever implements it.

Operations count a multiply-add as two.  What depends on the layers (the
matrices' parameters, attention's operations and bytes) comes from the
cell's architecture module (``archs/<bench_arch>.py``); this module adds
it up per token and holds the chip's peaks.
"""
from __future__ import annotations

import json
from pathlib import Path

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


def prompt_flops(arch, conf: dict, S: int) -> float:
    """Forward operations of an S-token prefill: every layer's matrices on
    every position, causal attention, and the LM head at the last
    position, which is the only one whose logits are needed."""
    n = arch.n_params(conf)
    return float(2 * n["layers"] * S + arch.prefill_attention(conf, S)[0]
                 + 2 * n["head"])


def decode_flops(arch, conf: dict, context: int) -> float:
    """Forward operations of one decoded token attending ``context``
    positions (itself included)."""
    n = arch.n_params(conf)
    return float(2 * n["layers"] + arch.decode_attention(conf, context)
                 + 2 * n["head"])


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
