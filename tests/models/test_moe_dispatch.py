"""MoE dispatch implementations: grouped (dropless, sorted by expert) vs
dense (the masked reference), and the router's semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.layers as L
from repro.configs import get_config


def _moe(arch, impl, **over):
    cfg = get_config(arch, smoke=True).scaled(dtype="float32", moe_impl=impl,
                                              **over)
    return cfg, L.init_moe(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b",
                                  "deepseek-v2-lite-16b"])
def test_grouped_matches_dense(arch):
    cfg, p = _moe(arch, "grouped")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    yd = L.apply_moe(p, x, cfg.scaled(moe_impl="dense"))
    yg = L.apply_moe(p, x, cfg)
    # float32 both ways; only the order of the sums differs
    np.testing.assert_allclose(np.asarray(yg), np.asarray(yd),
                               rtol=2e-4, atol=2e-5)


def test_grouped_drops_nothing_under_a_skewed_router():
    # 600 tokens, every one routed to expert 0 among its top-k: a capacity
    # of 1.25 * T * k / E per expert would drop most of expert 0's rows
    cfg, p = _moe("deepseek-v2-lite-16b", "grouped")
    x = 1.0 + jax.random.normal(jax.random.PRNGKey(2),
                                (1, 600, cfg.d_model), jnp.float32)
    p = dict(p, router=p["router"].at[:, 0].add(0.1))
    _, idx = L.route(p, x[0], cfg)
    assert bool(jnp.all(jnp.any(idx == 0, -1)))
    y = L.apply_moe(p, x, cfg)
    yd = L.apply_moe(p, x, cfg.scaled(moe_impl="dense"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd),
                               rtol=2e-4, atol=2e-5)


def test_grouped_moe_grads_flow():
    cfg, p = _moe("phi3.5-moe-42b-a6.6b", "grouped")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model),
                          jnp.float32)

    def loss(p_):
        return (L.apply_moe(p_, x, cfg) ** 2).sum()
    g = jax.grad(loss)(p)
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch,normed", [("deepseek-v2-lite-16b", False),
                                         ("phi3.5-moe-42b-a6.6b", True)])
def test_router_takes_top_k_of_the_full_softmax(arch, normed):
    cfg = get_config(arch)
    rng = np.random.default_rng(4)
    x = rng.normal(size=(5, 16)).astype(np.float32)
    w = rng.normal(size=(16, cfg.n_experts)).astype(np.float32)
    gates, idx = L.route({"router": jnp.asarray(w)}, jnp.asarray(x), cfg)
    logits = x.astype(np.float64) @ w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want_idx = np.argsort(-probs, -1)[:, :cfg.top_k]
    want = np.take_along_axis(probs, want_idx, -1)
    if normed:
        want = want / want.sum(-1, keepdims=True)
    assert cfg.norm_topk_prob is normed
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-5)
    assert (np.asarray(gates).sum(-1) < 1.0).all() != normed
