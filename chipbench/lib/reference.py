"""The comparison with the plain reference: how far each served token's
logit lies below the reference's best at its position.

The reference forward itself belongs to the cell's architecture module
(``archs/<bench_arch>.py``, its ``logits_at``), written from the published
definition and importing nothing of the program; this module pads a
request to one shape per cell, runs that forward at ``highest`` matmul
precision, and reads the gaps.  ``quantize`` and ``matmul`` are the
controls' arithmetic that every architecture module shares: a control
(``quant="int8"`` or ``"fp8"``) is the reference in the precision below
the configuration's bfloat16, each matmul's operands quantized with a
float32 scale per slice.
"""
from __future__ import annotations

from functools import lru_cache


def quantize(x, axis, quant):
    """``x`` rounded to ``quant`` with a scale per slice along ``axis``."""
    import jax.numpy as jnp
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / top
    if quant == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x, w, quant):
    if quant:
        x, w = quantize(x, -1, quant), quantize(w, 0, quant)
    return x @ w


@lru_cache(maxsize=4)
def _compiled(arch, conf_items, quant):
    import jax
    conf = dict(conf_items)
    return jax.jit(lambda p, t, r: arch.logits_at(p, conf, t, r, quant))


def served_gaps(arch, params, conf, prompt, served, pad_to, rows_to,
                controls=()):
    """For each served token, how far its logit in ``arch``'s reference
    lies below the reference's best at that position; for each control in
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
        ref = np.asarray(_compiled(arch, conf_items, None)(*args))[:n]
        picks = {q: np.asarray(_compiled(arch, conf_items, q)(*args))[:n]
                 .argmax(-1) for q in controls}
    best, at = ref.max(-1), np.arange(n)
    return best - ref[at, np.asarray(served)], \
        {q: best - ref[at, p] for q, p in picks.items()}
