"""Operations and bytes from shapes, against hand counts."""
import json

import pytest

from chipbench import run
from chipbench.lib import work

DENSE = run.load("archs", "dense")
CONF = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "vocab_size": 10, "kv_cache_dtype": "int8", "name": "hand"}


def test_params_by_hand():
    # q and o: 8 x 8 each; k and v: 8 x 4 each; MLP: 3 x 8 x 16
    n = DENSE.n_params(CONF)
    assert n["layers"] == 2 * (64 + 64 + 32 + 32 + 384)
    assert n["head"] == 80


def test_prefill_attention_by_hand():
    # S=3: 6 causal pairs; 4 heads x 4 * hd=2 ops each, 2 layers
    flops, nbytes = DENSE.prefill_attention(CONF, 3)
    assert flops == 2 * 4 * 4 * 2 * 6
    # Q, O per query head and K, V per kv head: (2*4 + 2*2) * S*hd * 2 B
    assert nbytes == 2 * (2 * 4 + 2 * 2) * 3 * 2 * 2


def test_forward_flops_by_hand():
    layers = 2 * (64 + 64 + 32 + 32 + 384)
    assert work.prompt_flops(DENSE, CONF, 3) == 2 * layers * 3 \
        + 2 * 4 * 4 * 2 * 6 + 2 * 80
    assert work.decode_flops(DENSE, CONF, 5) == 2 * layers \
        + 2 * 4 * 4 * 2 * 5 + 2 * 80


# (prompt_flops at 512 and 4096, prefill_attention at 512 and 4096,
# decode_flops at 4224) as the counts read before they moved into
# archs/dense.py: what mfu.serve and flash_roofline read may not move
COUNTS = {
    "internlm2-1.8b": (1572387422208.0, 14019554967552.0,
                       (25820135424.0, 150994944.0),
                       (1649670094848.0, 1207959552.0), 4229431296.0),
    "deepseek-7b": (3141596610560.0, 26930787123200.0,
                    (32275169280.0, 251658240.0),
                    (2062087618560.0, 2013265920.0), 7948206080.0),
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_cell_counts_unmoved(name):
    conf = json.loads((run.BENCH / "configs" / f"{name}.json").read_text())
    arch = run.load_arch(conf)
    assert (work.prompt_flops(arch, conf, 512),
            work.prompt_flops(arch, conf, 4096),
            arch.prefill_attention(conf, 512),
            arch.prefill_attention(conf, 4096),
            work.decode_flops(arch, conf, 4224)) == COUNTS[name]


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 10.0, peak) == (10.0, "flops")
    assert work.roofline_s(10.0, 1000.0, peak) == (100.0, "bytes")


def test_peaks_know_v5e_and_refuse_others():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
