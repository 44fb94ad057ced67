"""
swiglu — generated by repro.core (AscendCraft-style DSL-guided transcompilation, codegen v3).

category : activation
strategy : elementwise: flat span partition, pipelined tiles
backend  : pipelined  (BlockSpec-pipelined VMEM queues)

Host plan (Pass 1) for the generation shapes {'gate': (134217728,), 'up': (134217728,), 'output': (134217728,)}:
  gate_numel = 134217728
  n_cores = 32
  tile_length = 4096
  core_span = 131072
  padded_numel = 134217728
  per_core = 4194304
  n_tiles = 1024

Transcompilation pass log:
  - pass0/validate: ok (0 warnings)
  - pass2/init: backend=pipelined; TQue(in)=['gate_t', 'up_t'] TQue(out)=['y1'] TBuf=[]
  - pass4/align: GM layout padded for ['gate', 'output', 'up']
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rup(x, m):
    return -(-int(x) // int(m)) * int(m)


def _numel(shape):
    return int(math.prod(shape))


def _pad_to(arr, padded_shape, value):
    widths = [(0, int(p) - int(s)) for s, p in zip(arr.shape, padded_shape)]
    if any(w[1] for w in widths):
        return jnp.pad(arr, widths, constant_values=value)
    return arr


def _unpad(flat, padded_shape, orig_shape):
    a = flat[: _numel(padded_shape)].reshape(padded_shape)
    if tuple(padded_shape) != tuple(orig_shape):
        a = a[tuple(slice(0, int(s)) for s in orig_shape)]
    return a

def _plan(shapes):
    """Host function: core partitioning + tiling strategy (pass 1)."""
    gate_numel = shapes['gate'][0]
    n_cores = 32  # fixed vector-core count
    tile_length = min(4096, (-(-(-(-(134217728) // (32))) // (1024)) * 1024))  # tile so 3 live tiles fit the UB/VMEM budget; lane-aligned by Pass-4 padding
    core_span = 131072  # GM padded to a multiple of this (pass 4)
    padded_numel = (-(-(134217728) // (131072)) * 131072)
    per_core = 4194304
    n_tiles = 1024
    return dict(gate_numel=gate_numel, n_cores=n_cores, tile_length=tile_length, core_span=core_span, padded_numel=padded_numel, per_core=per_core, n_tiles=n_tiles)


def make(shapes, interpret=None):
    """Build the jitted kernel for `shapes` (dict: tensor name -> shape)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shapes = {k: tuple(int(x) for x in v) for k, v in shapes.items()}
    # ---- pass 4: flatten + pad the flat span (GM layout) ----
    shapes_flat = {k: (_numel(v),) for k, v in shapes.items()}
    p0 = _plan(shapes_flat)
    padded = dict(shapes_flat)
    padded['gate'] = (_rup(shapes_flat['gate'][0], p0['core_span']),)
    padded['up'] = (_rup(shapes_flat['up'][0], p0['core_span']),)
    padded['output'] = (_rup(shapes_flat['output'][0], p0['core_span']),)
    p = _plan(padded)
    gate_numel = p['gate_numel']
    n_cores = p['n_cores']
    tile_length = p['tile_length']
    core_span = p['core_span']
    padded_numel = p['padded_numel']
    per_core = p['per_core']
    n_tiles = p['n_tiles']
    gate_pnumel = _numel(padded['gate'])
    up_pnumel = _numel(padded['up'])
    output_pnumel = _numel(padded['output'])

    # ---- pass 2: TQue -> BlockSpec-pipelined VMEM blocks ----
    _in_specs = [
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
    ]
    _out_specs = [
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
    ]
    _out_shape = [
        jax.ShapeDtypeStruct((output_pnumel,), jnp.float32),
    ]

    def swiglu_kernel(_q_0_gate_t, _q_1_up_t, _o_output):
        # ---- copyin (double-buffered by the Pallas pipeline) ----
        gate_t = _q_0_gate_t[...]
        up_t = _q_1_up_t[...]
        # ---- compute ----
        y1 = jax.nn.silu(gate_t)
        y1 = (y1 * up_t)
        # ---- copyout ----
        _o_output[...] = y1

    _fn = pl.pallas_call(
        swiglu_kernel,
        grid=(n_cores, n_tiles,),
        in_specs=_in_specs,
        out_specs=_out_specs,
        out_shape=_out_shape,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel',)),
    )

    def _run(gate, up):
        _f_gate = jnp.asarray(gate, jnp.float32)
        _f_gate = _f_gate.reshape(-1)
        _f_gate = _pad_to(_f_gate, padded['gate'], 0.0)
        _f_up = jnp.asarray(up, jnp.float32)
        _f_up = _f_up.reshape(-1)
        _f_up = _pad_to(_f_up, padded['up'], 0.0)
        _res = _fn(_f_gate, _f_up)
        _out_output = _res[0].reshape(-1)[: _numel(shapes['output'])].reshape(shapes['output'])
        return _out_output
    return jax.jit(_run)


@functools.lru_cache(maxsize=32)
def _cached_make(shape_key, interpret):
    return make(dict(shape_key), interpret=interpret)


def swiglu(gate, up, out_shapes=None, interpret=None):
    """Convenience entry point (builds & caches per shape)."""
    _arrs = [gate, up]
    shapes = {}
    shapes['gate'] = tuple(_arrs[0].shape)
    shapes['up'] = tuple(_arrs[1].shape)
    _p0 = _plan({k: (_numel(v),) for k, v in shapes.items()})
    shapes['output'] = tuple((out_shapes or {}).get('output', tuple(_arrs[0].shape)))
    key = tuple(sorted(shapes.items()))
    fn = _cached_make(key, interpret)
    return fn(gate, up)
