"""Ahead-of-time compiles of the main path's generated kernels for a
described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a chip
that is described, not attached, and refuses what Mosaic would refuse on
the device (unaligned blocks, non-integer iotas, loads from ``pl.ANY``
refs).  The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fusion.chain import CHAINS, build_fused
from repro.core.lowering.pipeline import transcompile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile_chain(chain, shapes, one_chip):
    """Build ``chain`` at ``shapes``, compile its Mosaic kernel for the
    described chip and return the compiled HLO text."""
    spec = CHAINS[chain]
    art = transcompile(build_fused(spec, shapes), verify_against_interp=False)
    args = [jax.ShapeDtypeStruct(shapes[name], jnp.float32,
                                 sharding=one_chip)
            for name, _ in spec.inputs]
    fn = jax.jit(functools.partial(art.entry, interpret=False))
    return fn.lower(*args).compile().as_text()


def _flash_shapes(s, d=128):
    return {"q": (s, d), "k": (s, d), "mask": (s, s), "v": (s, d),
            "output": (s, d)}


def _row_shapes(chain, rows, cols):
    names = dict(CHAINS[chain].inputs)
    shapes = {n: ((cols,) if rank == 1 else (rows, cols))
              for n, rank in names.items()}
    shapes.update({n: (rows, cols) for n in CHAINS[chain].outputs})
    return shapes


@pytest.mark.parametrize("s", [512, 4096])
def test_flash_chain_compiles_resident(s, one_chip, no_persistent_cache):
    assert "tpu_custom_call" in _compile_chain("flash_attention",
                                               _flash_shapes(s), one_chip)


@pytest.mark.parametrize("chain", ["add_rmsnorm", "rmsnorm_swiglu"])
def test_row_chain_compiles(chain, one_chip, no_persistent_cache):
    text = _compile_chain(chain, _row_shapes(chain, 2048, 2048), one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", ["relu", "adamw"])
def test_flat_task_compiles_at_check_shape(name, one_chip,
                                           no_persistent_cache):
    """Small flat tasks: the per-core tile must be a whole number of
    (8, 128) tiles, or Mosaic refuses the rank-1 block."""
    from repro.bench.tasks import suite
    from repro.core import planner
    task = {t.name: t for t in suite()}[name]
    art, _ = planner.resolve_and_build(
        task, planner.PLANNER_REGISTRY[task.op], "default", None,
        task.check_shapes, check_shapes=None, verify_against_interp=False)
    args = [jax.ShapeDtypeStruct(task.check_shapes[tp.name], jnp.float32,
                                 sharding=one_chip)
            for tp in task.input_specs]
    fn = jax.jit(functools.partial(art.entry, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="streaming flash chain reads pl.ANY refs "
                          "directly: 'Loads are only allowed on VMEM and "
                          "SMEM references' (ROADMAP R2a)")
def test_flash_chain_compiles_streaming(one_chip, no_persistent_cache):
    assert "tpu_custom_call" in _compile_chain("flash_attention",
                                               _flash_shapes(8192), one_chip)


@pytest.mark.parametrize("s", [512, 3072])
def test_flash_chain_compiles_resident_at_mla_head_dim(s, one_chip,
                                                       no_persistent_cache):
    """DeepSeek-V2's q/k head dim 192 (v zero-padded to it): resident up
    to 3072 keys; at 4096 the chain streams and attention runs the
    reference (tests/models/test_mla.py)."""
    assert "tpu_custom_call" in _compile_chain(
        "flash_attention", _flash_shapes(s, d=192), one_chip)


def test_grouped_experts_read_the_layer_stack_in_place(one_chip,
                                                       no_persistent_cache):
    """DeepSeek-V2-Lite's decode MoE at published widths: the grouped
    products read one layer's 64 experts out of the (8, 64, ...) stack
    without copying them (1.06 GB a layer) into a temporary."""
    import repro.models.layers as L
    bf16 = jnp.bfloat16

    def shape(s, dt=bf16):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    ex = {"w_gate": shape((8, 64, 2048, 1408)),
          "w_up": shape((8, 64, 2048, 1408)),
          "w_down": shape((8, 64, 1408, 2048))}
    fn = jax.jit(lambda e, x, g, i: L._experts_grouped(dict(e, layer=3), x,
                                                       g, i))
    compiled = fn.lower(ex, shape((16, 2048)), shape((16, 6), jnp.float32),
                        shape((16, 6), jnp.int32)).compile()
    assert "ragged" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
