"""The one request generator: a serving mix is a JSON file of parameters.

Lengths come from lognormal distributions, read at stratified quantiles
rather than drawn: every block of ``block`` consecutive requests holds the
same multiset of prompt and output lengths, and the seed chooses the token
ids and, unless the mix says ``"order": "fixed"``, the order inside each
block.  So any stretch of the queue that a window serves sees nearly the
same work on every seed.  With ``"order": "fixed"`` the order is one
drawn once for the mix, the same for every seed, so that every seed sends
the same lengths in the same sequence: in a closed loop the tails of the
latencies depend on which requests finish together, and so on the order.
Prompt lengths snap up to the next step of ``prompt.ladder`` where the
mix has one (a length above the top step takes the top step), and
otherwise round and clip to ``[prompt.min, prompt.max]``; output lengths
round and clip to ``[output.min, output.max]``.  With ``prefix: {count,
length}`` the prompts share prefixes: request i begins with the first
``length`` tokens of prefix ``i % count``, and the rest of it is its own.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class RequestSpec:
    uid: int
    prompt: np.ndarray          # (S,) int32 token ids
    max_new_tokens: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named purpose, from a seed of any size."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def jax_key(seed: int):
    """The key the weights are made from."""
    import jax
    return jax.random.PRNGKey(int(rng_for(seed, "weights").integers(2**31)))


def lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 0.5) / n of a lognormal."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return median * np.exp(sigma * z)


def snap_up(values: np.ndarray, ladder) -> np.ndarray:
    steps = np.asarray(sorted(ladder))
    idx = np.minimum(np.searchsorted(steps, values, side="left"),
                     len(steps) - 1)
    return steps[idx]


def block_lengths(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """The multiset of (prompt, output) lengths of one block, unordered."""
    n = int(mix["block"])
    p, o = mix["prompt"], mix["output"]
    q = lognormal_quantiles(p["median"], p["sigma"], n)
    prompts = snap_up(q, p["ladder"]) if "ladder" in p else \
        np.clip(np.rint(q), p["min"], p["max"])
    outs = np.clip(np.rint(lognormal_quantiles(o["median"], o["sigma"], n)),
                   o["min"], o["max"]).astype(np.int64)
    return prompts.astype(np.int64), outs


def requests(mix: dict, seed: int, vocab: int, n: int) -> list[RequestSpec]:
    """The first ``n`` requests of the mix's queue for ``seed``."""
    prompts, outs = block_lengths(mix)
    order = rng_for(0 if mix.get("order") == "fixed" else seed, "order")
    ids = rng_for(seed, "tokens")
    pre = mix.get("prefix")
    heads = [ids.integers(0, vocab, int(pre["length"]), dtype=np.int32)
             for _ in range(int(pre["count"]))] if pre else []
    out = []
    for b in range(math.ceil(n / len(prompts))):
        pp = order.permutation(prompts)
        oo = order.permutation(outs)
        for s, m in zip(pp, oo):
            if len(out) == n:
                break
            head = heads[len(out) % len(heads)][:int(s)] if heads else \
                np.zeros(0, np.int32)
            tail = ids.integers(0, vocab, int(s) - len(head), dtype=np.int32)
            out.append(RequestSpec(uid=len(out),
                                   prompt=np.concatenate([head, tail]),
                                   max_new_tokens=int(m)))
    return out


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix sends, whatever the seed."""
    return sorted({int(s) for s in block_lengths(mix)[0]})


def warmup_requests(mix: dict, vocab: int) -> list[RequestSpec]:
    """One short request per prompt length the mix sends, and no other."""
    rng = rng_for(0, "warmup")
    return [RequestSpec(uid=-1 - i,
                        prompt=rng.integers(0, vocab, s, dtype=np.int32),
                        max_new_tokens=2)
            for i, s in enumerate(prompt_lengths(mix))]


def max_len(mix: dict) -> int:
    """Cache positions a slot needs: the longest prompt plus the longest
    output."""
    prompts, outs = block_lengths(mix)
    return int(prompts.max() + outs.max())
