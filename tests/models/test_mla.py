"""DeepSeek-V2's latent attention: YaRN rotary scaling and softmax scale as
published, and the absorbed latent decode against the up-projected form."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.configs import get_config

V2 = get_config("deepseek-v2-lite-16b")


def test_yarn_range_and_frequencies_as_published():
    s = V2.rope_scaling
    assert L.yarn_range(64, 1e4, s) == (10, 23)
    i = np.arange(32)
    extra = 1e4 ** (-2.0 * i / 64)
    keep = 1.0 - np.clip((i - 10) / 13, 0.0, 1.0)
    want = extra / 40 * (1 - keep) + extra * keep
    np.testing.assert_allclose(np.asarray(L.rope_inv_freq(64, 1e4, s)),
                               want, rtol=1e-6)
    # cos/sin keep their size: mscale(40, 0.707) / mscale(40, 0.707)
    cos, sin = L.rope_freqs(64, 1e4, jnp.arange(3), s)
    np.testing.assert_allclose(np.asarray(cos ** 2 + sin ** 2), 1.0,
                               rtol=1e-6)


def test_softmax_scale_as_published():
    want = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert abs(L.mla_softmax_scale(V2) - want) < 1e-12
    assert round(L.mla_softmax_scale(V2), 6) == 0.114721


def test_interleaved_rope_rotates_adjacent_pairs():
    x = jnp.arange(1.0, 9.0).reshape(1, 1, 1, 8)
    ang = jnp.asarray([[0.3, 0.5, 0.7, 1.1]])
    got = L.apply_rope_interleaved(x, jnp.cos(ang), jnp.sin(ang))
    a, b = np.asarray(x).reshape(4, 2).T            # pairs (x0, x1), ...
    c, s = np.cos(np.asarray(ang[0])), np.sin(np.asarray(ang[0]))
    np.testing.assert_allclose(np.asarray(got).reshape(8),
                               np.concatenate([a * c - b * s,
                                               b * c + a * s]), rtol=1e-6)


def test_absorbed_decode_equals_up_projected_attention():
    cfg = get_config("deepseek-v2-lite-16b", smoke=True).scaled(
        dtype="float32")
    p = L.init_mla(jax.random.PRNGKey(0), cfg)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    B, T = 2, 12
    nh, dn, dr, dv = (cfg.n_heads, cfg.nope_head_dim, cfg.rope_head_dim,
                      cfg.v_head_dim)
    cache = {"c_kv": jax.random.normal(ks[0], (B, T, cfg.kv_lora)),
             "k_pe": jax.random.normal(ks[1], (B, T, dr)),
             "length": jnp.asarray([4, 9], jnp.int32)}
    x = jax.random.normal(ks[2], (B, 1, cfg.d_model))
    pos = cache["length"][:, None]
    out, new = L.apply_mla(p, x, cfg, positions=pos, cache=cache)

    # the published form: every cached latent up-projected to per-head
    # keys and values, then ordinary attention over the live positions
    c_kv, k_pe = new["c_kv"], new["k_pe"]
    kv = (c_kv @ p["wkv_b"]).reshape(B, T, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None], (B, T, nh, dr))], -1)
    q = (x @ p["wq"]).reshape(B, 1, nh, dn + dr)
    cos, sin = L.rope_freqs(dr, cfg.rope_theta, pos, cfg.rope_scaling)
    q = jnp.concatenate([q[..., :dn],
                         L.apply_rope_interleaved(q[..., dn:], cos, sin)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * L.mla_softmax_scale(cfg)
    live = jnp.arange(T)[None, :] <= cache["length"][:, None]
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., dn:])
    want = o.reshape(B, 1, nh * dv) @ p["wo"]
    # float32 both ways: the same sums in another order (W_UK and W_UV
    # applied to the query and the output instead of every position)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert new["length"].tolist() == [5, 10]


def test_mla_cache_refuses_int8():
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    assert cfg.kv_cache_dtype == "model"
    with pytest.raises(ValueError, match="int8"):
        L.init_mla_cache(cfg.scaled(kv_cache_dtype="int8"), 1, 8)


@pytest.mark.parametrize("S,chain", [(3072, True), (4096, False)])
def test_mla_prefill_runs_the_chain_where_it_is_resident(S, chain):
    from repro.kernels.flash_attention import ops as fa_ops
    q = jax.ShapeDtypeStruct((1, S, 1, 192), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q: fa_ops.attention(
        q, q, q, sm_scale=0.1, impl="pallas"))(q)
    assert ("pallas_call" in str(jaxpr)) is chain
