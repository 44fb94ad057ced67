"""The request generator: deterministic in the seed, on its ladder and
clips, the same sizes in every block whatever the seed, and the same
sequence of sizes for every seed where the mix fixes the order."""
import collections
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.lib import traffic

MIXES = Path(__file__).resolve().parents[1] / "mixes"
BIG_SEED = 2**31 + 12345


def load(name):
    return json.loads((MIXES / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["prefill_heavy", "decode_heavy"])
def test_same_seed_same_requests(name):
    mix = load(name)
    a = traffic.requests(mix, BIG_SEED, 1000, 120)
    b = traffic.requests(mix, BIG_SEED, 1000, 120)
    assert [(r.max_new_tokens, r.prompt.tolist()) for r in a] == \
        [(r.max_new_tokens, r.prompt.tolist()) for r in b]
    c = traffic.requests(mix, BIG_SEED + 1, 1000, 120)
    assert [r.prompt[0] for r in a] != [r.prompt[0] for r in c]
    lengths = [[(len(r.prompt), r.max_new_tokens) for r in x] for x in (a, c)]
    if mix.get("order") == "fixed":
        assert lengths[0] == lengths[1]
    else:
        assert lengths[0] != lengths[1]


@pytest.mark.parametrize("name", ["prefill_heavy", "decode_heavy"])
def test_lengths_on_ladder_and_clips(name):
    mix = load(name)
    reqs = traffic.requests(mix, 7, 1000, 3 * mix["block"])
    assert {len(r.prompt) for r in reqs} <= set(mix["prompt"]["ladder"])
    outs = [r.max_new_tokens for r in reqs]
    assert min(outs) >= mix["output"]["min"]
    assert max(outs) <= mix["output"]["max"]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in reqs)


@pytest.mark.parametrize("name", ["prefill_heavy", "decode_heavy"])
def test_every_block_holds_the_same_sizes(name):
    mix = load(name)
    n = mix["block"]
    want_p, want_o = traffic.block_lengths(mix)
    for seed in (1, 2, BIG_SEED):
        reqs = traffic.requests(mix, seed, 100, 3 * n)
        for b in range(3):
            blk = reqs[b * n:(b + 1) * n]
            assert collections.Counter(len(r.prompt) for r in blk) == \
                collections.Counter(want_p.tolist())
            assert collections.Counter(r.max_new_tokens for r in blk) == \
                collections.Counter(want_o.tolist())


def test_lognormal_snaps_up():
    mix = {"block": 4, "prompt": {"ladder": [10, 20, 40], "median": 15,
                                  "sigma": 1e-9},
           "output": {"median": 5, "sigma": 1e-9, "min": 1, "max": 9}}
    p, o = traffic.block_lengths(mix)
    assert p.tolist() == [20] * 4 and o.tolist() == [5] * 4
    assert traffic.snap_up(np.array([1, 10, 11, 999]), [10, 20]).tolist() \
        == [10, 10, 20, 20]


@pytest.mark.parametrize("name", ["prefill_heavy", "decode_heavy"])
def test_warmup_covers_every_prompt_length_sent(name):
    mix = load(name)
    warm = sorted(len(r.prompt) for r in traffic.warmup_requests(mix, 1000))
    sent = {len(r.prompt) for seed in (3, BIG_SEED)
            for r in traffic.requests(mix, seed, 1000, 2 * mix["block"])}
    assert warm == sorted(sent)
    assert traffic.max_len(mix) == max(mix["prompt"]["ladder"]) + \
        mix["output"]["max"]


FREE = {"block": 20, "prompt": {"median": 300, "sigma": 0.8, "min": 64,
                                "max": 1024},
        "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 20}}


def test_free_lengths_round_and_clip_without_a_ladder():
    p, _ = traffic.block_lengths(FREE)
    assert p.min() >= 64 and p.max() <= 1024 and len(set(p.tolist())) > 10
    warm = traffic.warmup_requests(FREE, 1000)
    assert sorted(len(r.prompt) for r in warm) == sorted(set(p.tolist()))
    assert traffic.max_len(FREE) == int(p.max()) + 20


def test_shared_prefixes():
    mix = dict(FREE, prefix={"count": 3, "length": 50})
    reqs = traffic.requests(mix, BIG_SEED, 1000, 12)
    for i, r in enumerate(reqs):
        j = i % 3
        assert (r.prompt[:50] == reqs[j].prompt[:50]).all()
        if i >= 3:
            assert not (r.prompt[:50] == reqs[(j + 1) % 3].prompt[:50]).all()
    plain = traffic.requests(FREE, BIG_SEED, 1000, 12)
    assert [len(r.prompt) for r in reqs] == [len(r.prompt) for r in plain]
