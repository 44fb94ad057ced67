"""Flash attention via the GENERATED fusion chain: shape/dtype sweep vs the
pure-jnp oracle (``ref.py``).  The forward no longer runs a hand-written
Pallas kernel — it compiles the proposer-derived flash_attention chain per
(Sq, Skv, D) slice geometry (DESIGN.md §13), so this file is the
end-to-end differential gate for that path: MHA/GQA/MQA head mappings,
causal and full masks, cross-length KV, explicit sm_scale folding, and a
bit-for-bit check against the reference at a resident-form geometry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (flash_attention_fwd,
                                           mha_reference, decode_reference)
from repro.kernels.flash_attention.ops import flash_attention


def _mk(B, Sq, Skv, Hq, Hkv, D, dtype, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Sq, Hq, D), dtype) * 0.5
    k = jnp.asarray(rng.randn(B, Skv, Hkv, D), dtype) * 0.5
    v = jnp.asarray(rng.randn(B, Skv, Hkv, D), dtype) * 0.5
    return q, k, v


SHAPES = [
    # (B, Sq, Skv, Hq, Hkv, D)
    (1, 128, 128, 2, 2, 64),      # MHA square
    (2, 256, 256, 4, 2, 64),      # GQA 2:1
    (1, 128, 512, 8, 1, 32),      # MQA, cross longer KV
    (2, 384, 384, 4, 4, 128),     # non-pow2 seq
    (1, 100, 100, 2, 1, 32),      # unaligned: rows and keys pad to 128
    (1, 64, 200, 2, 2, 32),       # unaligned cross-length KV
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference_f32(shape, causal):
    B, Sq, Skv, Hq, Hkv, D = shape
    q, k, v = _mk(B, Sq, Skv, Hq, Hkv, D, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bit_exact_at_resident_geometry():
    """At a geometry where the whole row block is VMEM-resident the chain
    degenerates to the same dot-softmax-dot sequence the reference runs:
    the generated kernel must match ``mha_reference`` bit for bit."""
    q, k, v = _mk(2, 16, 16, 4, 2, 16, jnp.float32)
    out = flash_attention_fwd(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_flash_traces_again_at_one_geometry():
    """Two jitted programs at the same (Sq, Skv): nothing cached by the
    first trace may leak into the second (serving traces prefill once per
    prompt length, then again for every new program at that length)."""
    q, k, v = _mk(1, 100, 100, 2, 1, 32, jnp.float32)
    a = jax.jit(lambda q: flash_attention_fwd(q, k, v))(q)
    b = jax.jit(lambda q: flash_attention_fwd(q, k, v) * 2.0)(q)
    np.testing.assert_allclose(np.asarray(b), 2.0 * np.asarray(a),
                               rtol=1e-6, atol=1e-6)


def test_flash_explicit_sm_scale_folded_into_q():
    """The chain bakes the traced qk scale; an arbitrary sm_scale must be
    folded into q without changing the result vs the reference."""
    q, k, v = _mk(1, 64, 64, 2, 2, 32, jnp.float32)
    for s in (0.5, 0.07, 1.0):
        out = flash_attention_fwd(q, k, v, causal=True, sm_scale=s)
        ref = mha_reference(q, k, v, causal=True, sm_scale=s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_dtypes(dtype):
    q, k, v = _mk(1, 128, 128, 2, 2, 64, dtype)
    out = flash_attention_fwd(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    assert out.dtype == dtype


def test_flash_custom_vjp_grads_match_reference():
    q, k, v = _mk(1, 128, 128, 2, 2, 32, jnp.float32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, True, None) ** 2).sum()

    def f_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_decode_reference_consistent_with_full():
    """Decode (1 token vs cache) must equal the last row of full attention."""
    B, S, H, Hkv, D = 2, 64, 4, 2, 32
    q, k, v = _mk(B, S, S, H, Hkv, D, jnp.float32)
    full = mha_reference(q, k, v, causal=True)
    out = decode_reference(q[:, -1:], k, v,
                           jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-5, atol=2e-5)
