"""A run with its timed path broken underneath must come out not correct:
tokens altered where the engine produces them, and a decode step that
returns its cache unchanged."""
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests.tiny import CELL, FakeDevice, make_root


def alter_tokens(engine):
    """Each decode step puts one slot's top logit, round robin, on another
    token, so that every request longer than the slot count gets a wrong
    token."""
    decode, calls = engine._decode, [0]

    def broken(params, tokens, caches):
        logits, new = decode(params, tokens, caches)
        b = calls[0] % logits.shape[0]
        calls[0] += 1
        top = jnp.argmax(logits[b, 0])
        return logits.at[b, 0, (top + 1) % logits.shape[-1]].set(1e4), new
    engine._decode = broken


def stale_cache(engine):
    """Every decode step returns the cache it was given."""
    decode = engine._decode

    def broken(params, tokens, caches):
        logits, _ = decode(params, tokens, caches)
        return logits, caches
    engine._decode = broken


@pytest.mark.parametrize("fault", [alter_tokens, stale_cache],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    root = make_root(tmp_path)
    out = run.run_cell(CELL, 41, 1.5, False, root=root,
                       devices=[FakeDevice()], fault=fault)
    c = out["checks"]["token_gap"]
    assert out["correct"] is False and c["value"] > c["limit"]
