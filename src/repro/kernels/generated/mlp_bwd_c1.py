"""
mlp_bwd_c1_fused — generated by repro.core (AscendCraft-style DSL-guided transcompilation, codegen v3).

category : fused
strategy : fused chain (one UB visit, Store/Load round trips deleted): mlp_bwd_c1_s0_mul -> mlp_bwd_c1_s1_mul -> mlp_bwd_c1_s2_add
backend  : pipelined  (BlockSpec-pipelined VMEM queues)

Host plan (Pass 1) for the generation shapes {'input': (16384, 4096), 'x1': (16384, 4096), 'x2': (16384, 4096), 'output': (16384, 4096), 'x3': (16384, 4096)}:
  input_numel = 67108864
  input_dim1 = 4096
  cols_padded_unit = 128
  rows = 16384
  block_rows = 64
  n_blocks = 256

Transcompilation pass log:
  - pass0/validate: ok (0 warnings)
  - pass2/init: backend=pipelined; TQue(in)=['f0_input_t', 'f0_x1_t', 'f1_x2_t', 'f2_x3_t'] TQue(out)=['f2_y1'] TBuf=['f0_y1', 'f1_y1']
  - pass4/align: GM layout padded for ['input', 'output', 'x1', 'x2', 'x3']
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
    input_numel = (shapes['input'][0] * shapes['input'][1])
    input_dim1 = shapes['input'][1]
    cols_padded_unit = 128  # lane alignment for the trailing axis (pass 4)
    rows = 16384
    block_rows = 64  # chain-wide row block: shared by every stage so the fusion pass can stitch identical GM spans
    n_blocks = 256
    return dict(input_numel=input_numel, input_dim1=input_dim1, cols_padded_unit=cols_padded_unit, rows=rows, block_rows=block_rows, n_blocks=n_blocks)


def make(shapes, interpret=None):
    """Build the jitted kernel for `shapes` (dict: tensor name -> shape)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shapes = {k: tuple(int(x) for x in v) for k, v in shapes.items()}
    p0 = _plan(shapes)
    # ---- pass 4: alignment & padding refinement (GM layout) ----
    padded = dict(shapes)
    _s = list(shapes['input']); _s[-1] = _rup(_s[-1], p0['cols_padded_unit'])
    padded['input'] = tuple(_s)
    _s = list(shapes['x1']); _s[-1] = _rup(_s[-1], p0['cols_padded_unit'])
    padded['x1'] = tuple(_s)
    _s = list(shapes['x2']); _s[-1] = _rup(_s[-1], p0['cols_padded_unit'])
    padded['x2'] = tuple(_s)
    _s = list(shapes['x3']); _s[-1] = _rup(_s[-1], p0['cols_padded_unit'])
    padded['x3'] = tuple(_s)
    _s = list(shapes['output']); _s[-1] = _rup(_s[-1], p0['cols_padded_unit'])
    padded['output'] = tuple(_s)
    p = _plan(padded)
    if not (p['rows'] % p['block_rows'] == 0):
        raise ValueError('rows must divide the generated core/block partition; regenerate the chain for this shape')
    if not (shapes['input'][-1] == 4096):
        raise ValueError('chain was specialized for a different trailing dimension; regenerate for this shape')
    input_numel = p['input_numel']
    input_dim1 = p['input_dim1']
    cols_padded_unit = p['cols_padded_unit']
    rows = p['rows']
    block_rows = p['block_rows']
    n_blocks = p['n_blocks']
    input_pnumel = _numel(padded['input'])
    x1_pnumel = _numel(padded['x1'])
    x2_pnumel = _numel(padded['x2'])
    x3_pnumel = _numel(padded['x3'])
    output_pnumel = _numel(padded['output'])

    # ---- pass 2: TQue -> BlockSpec-pipelined VMEM blocks ----
    _in_specs = [
        pl.BlockSpec((block_rows, input_dim1), lambda i: ((((((((1) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))) * i + (((((((0) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))), 0)),
        pl.BlockSpec((block_rows, input_dim1), lambda i: ((((((((1) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))) * i + (((((((0) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))), 0)),
        pl.BlockSpec((block_rows, input_dim1), lambda i: ((((((((1) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))) * i + (((((((0) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))), 0)),
        pl.BlockSpec((block_rows, input_dim1), lambda i: ((((((((1) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))) * i + (((((((0) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))), 0)),
    ]
    _out_specs = [
        pl.BlockSpec((block_rows, input_dim1), lambda i: ((((((((1) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))) * i + (((((((0) * (block_rows))) * (input_dim1))) // (block_rows * input_dim1))), 0)),
    ]
    _out_shape = [
        jax.ShapeDtypeStruct((output_pnumel // padded['output'][-1], padded['output'][-1]), jnp.float32),
    ]

    def mlp_bwd_c1_fused_kernel(_q_0_f0_input_t, _q_1_f0_x1_t, _q_2_f1_x2_t, _q_3_f2_x3_t, _o_output):
        # ---- copyin (double-buffered by the Pallas pipeline) ----
        f0_input_t = _q_0_f0_input_t[...]
        f0_x1_t = _q_1_f0_x1_t[...]
        f1_x2_t = _q_2_f1_x2_t[...]
        f2_x3_t = _q_3_f2_x3_t[...]
        # ---- compute ----
        f0_y1 = (f0_input_t * f0_x1_t)
        f1_y1 = (f1_x2_t * f0_y1)
        f2_y1 = (f2_x3_t + f1_y1)
        # ---- copyout ----
        _o_output[...] = f2_y1

    _fn = pl.pallas_call(
        mlp_bwd_c1_fused_kernel,
        grid=(n_blocks,),
        in_specs=_in_specs,
        out_specs=_out_specs,
        out_shape=_out_shape,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=('parallel',)),
    )

    def _run(input, x1, x2, x3):
        _f_input = jnp.asarray(input, jnp.float32)
        _f_input = _pad_to(_f_input, padded['input'], 0.0)
        _f_input = _f_input.reshape(-1)
        _f_x1 = jnp.asarray(x1, jnp.float32)
        _f_x1 = _pad_to(_f_x1, padded['x1'], 0.0)
        _f_x1 = _f_x1.reshape(-1)
        _f_x2 = jnp.asarray(x2, jnp.float32)
        _f_x2 = _pad_to(_f_x2, padded['x2'], 0.0)
        _f_x2 = _f_x2.reshape(-1)
        _f_x3 = jnp.asarray(x3, jnp.float32)
        _f_x3 = _pad_to(_f_x3, padded['x3'], 0.0)
        _f_x3 = _f_x3.reshape(-1)
        _res = _fn(_f_input.reshape((-1, padded['input'][-1])), _f_x1.reshape((-1, padded['x1'][-1])), _f_x2.reshape((-1, padded['x2'][-1])), _f_x3.reshape((-1, padded['x3'][-1])))
        _out_output = _unpad(_res[0].reshape(-1), padded['output'], shapes['output'])
        return _out_output
    return jax.jit(_run)


@functools.lru_cache(maxsize=32)
def _cached_make(shape_key, interpret):
    return make(dict(shape_key), interpret=interpret)


def mlp_bwd_c1_fused(input, x1, x2, x3, out_shapes=None, interpret=None):
    """Convenience entry point (builds & caches per shape)."""
    _arrs = [input, x1, x2, x3]
    shapes = {}
    shapes['input'] = tuple(_arrs[0].shape)
    shapes['x1'] = tuple(_arrs[1].shape)
    shapes['x2'] = tuple(_arrs[2].shape)
    shapes['x3'] = tuple(_arrs[3].shape)
    _p0 = _plan(shapes)
    shapes['output'] = tuple((out_shapes or {}).get('output', tuple(_arrs[0].shape)))
    key = tuple(sorted(shapes.items()))
    fn = _cached_make(key, interpret)
    return fn(input, x1, x2, x3)
