"""Architecture modules: the dense one makes the weights and the reference
logits it made before it was a module of its own, a configuration names
its module or is refused, and the harness outside ``archs/`` names no
architecture."""
import hashlib
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.lib import traffic
from chipbench.tests.tiny import TINY_CONF

DENSE = run.load("archs", "dense")
SEED = 2**31 + 77
# sha256 over the leaves, in tree order, of the tiny weights for SEED, as
# the harness made them before the dense layout moved into archs/dense.py
WEIGHTS = "28debc16e39f1bbcf307326273c00961f043046244721be05d5863c7a87b2e1a"
ROWS = [0, 17, 39]
# the reference's three largest logits (index, value) at ROWS of 40
# tokens, read before the move
TOP = {
    None: ([[99, 279, 415], [192, 200, 18], [271, 265, 167]],
           [[2.887077569961548, 2.809852123260498, 2.7060885429382324],
            [3.1860694885253906, 3.122244119644165, 2.821519374847412],
            [2.558223247528076, 2.4579951763153076, 2.2855334281921387]]),
    "int8": ([[99, 279, 415], [200, 192, 488], [271, 265, 167]],
             [[2.8554420471191406, 2.7790403366088867, 2.7348196506500244],
              [3.1885128021240234, 3.131195068359375, 2.881457805633545],
              [2.5748157501220703, 2.536513090133667, 2.312026023864746]]),
    "fp8": ([[99, 279, 415], [200, 488, 192], [265, 91, 271]],
            [[2.7907276153564453, 2.790076732635498, 2.708832263946533],
             [2.9800667762756348, 2.976297616958618, 2.942654609680176],
             [2.5575454235076904, 2.429077625274658, 2.3566999435424805]]),
}


@pytest.fixture(scope="module")
def params():
    return jax.jit(partial(DENSE.make_params, TINY_CONF))(
        traffic.jax_key(SEED))


def test_dense_weights_are_bit_identical(params):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS


@pytest.mark.parametrize("quant", [None, "int8", "fp8"])
def test_dense_reference_logits_unmoved(params, quant):
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, TINY_CONF["vocab_size"], 40, dtype=np.int32))
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(jax.jit(lambda p, t, r: DENSE.logits_at(
            p, TINY_CONF, t, r, quant))(params, tokens,
                                        jnp.asarray(ROWS, jnp.int32)))
    top = np.argsort(-lg, -1)[:, :3]
    want_top, want_val = TOP[quant]
    assert top.tolist() == want_top
    np.testing.assert_allclose(np.take_along_axis(lg, top, -1), want_val,
                               rtol=1e-6, atol=1e-6)


def test_configuration_without_bench_arch_is_refused():
    conf = {k: v for k, v in TINY_CONF.items() if k != "bench_arch"}
    with pytest.raises(KeyError, match="bench_arch"):
        run.load_arch(conf)


# what only an architecture module may know: its layers' keys and
# tensors, the program's model registry, and models by name
ARCH_WORDS = re.compile(
    r"hidden_size|intermediate_size|num_attention_heads|"
    r"num_key_value_heads|head_dim|num_hidden_layers|rms_norm_eps|"
    r"rope_theta|program_arch|get_config|ArchConfig|swiglu|\bdense\b|"
    r"\bgqa\b|\bmha\b|\bmla\b|kv_lora|experts|w_gate|\bwq\b|internlm|"
    r"deepseek", re.IGNORECASE)


@pytest.mark.parametrize("part", ["lib", "drivers", "metrics", "run.py",
                                  "calibrate.py"])
def test_harness_outside_archs_names_no_architecture(part):
    path = run.BENCH / part
    files = sorted(path.glob("*.py")) if path.is_dir() else [path]
    assert files
    for f in files:
        hits = ARCH_WORDS.findall(f.read_text())
        assert not hits, f"{f.name} names an architecture: {hits}"
