"""Operations and bytes from shapes, against hand counts."""
import pytest

from chipbench.lib import model, work

CONF = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "vocab_size": 10, "kv_cache_dtype": "int8", "name": "hand"}


def test_params_by_hand():
    # q and o: 8 x 8 each; k and v: 8 x 4 each; MLP: 3 x 8 x 16
    n = model.n_params(CONF)
    assert n["layers"] == 2 * (64 + 64 + 32 + 32 + 384)
    assert n["head"] == 80


def test_prefill_attention_by_hand():
    # S=3: 6 causal pairs; 4 heads x 4 * hd=2 ops each, 2 layers
    flops, nbytes = work.prefill_attention(CONF, 3)
    assert flops == 2 * 4 * 4 * 2 * 6
    # Q, O per query head and K, V per kv head: (2*4 + 2*2) * S*hd * 2 B
    assert nbytes == 2 * (2 * 4 + 2 * 2) * 3 * 2 * 2


def test_forward_flops_by_hand():
    layers = 2 * (64 + 64 + 32 + 32 + 384)
    assert work.prompt_flops(CONF, 3) == 2 * layers * 3 + 2 * 4 * 4 * 2 * 6 \
        + 2 * 80
    assert work.decode_flops(CONF, 5) == 2 * layers + 2 * 4 * 4 * 2 * 5 \
        + 2 * 80


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

