"""
adamw — generated by repro.core (AscendCraft-style DSL-guided transcompilation, codegen v3).

category : optimizer
strategy : elementwise: flat span partition, pipelined tiles
backend  : pipelined  (BlockSpec-pipelined VMEM queues)

Host plan (Pass 1) for the generation shapes {'param': (67108864,), 'grad': (67108864,), 'm': (67108864,), 'v': (67108864,)}:
  param_numel = 67108864
  n_cores = 32
  tile_length = 4096
  core_span = 131072
  padded_numel = 67108864
  per_core = 2097152
  n_tiles = 512

Transcompilation pass log:
  - pass0/validate: ok (0 warnings)
  - pass2/init: backend=pipelined; TQue(in)=['grad_t', 'm_t', 'param_t', 'v_t'] TQue(out)=['new_m1', 'new_p5', 'new_v2'] TBuf=['t3', 'u4', 'wd6']
  - pass4/align: GM layout padded for ['grad', 'm', 'param', 'v']
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
    param_numel = shapes['param'][0]
    n_cores = 32  # fixed vector-core count
    tile_length = min(4096, (-(-(-(-(67108864) // (32))) // (1024)) * 1024))  # tile so 4 live tiles fit the UB/VMEM budget; lane-aligned by Pass-4 padding
    core_span = 131072  # GM padded to a multiple of this (pass 4)
    padded_numel = (-(-(67108864) // (131072)) * 131072)
    per_core = 2097152
    n_tiles = 512
    return dict(param_numel=param_numel, n_cores=n_cores, tile_length=tile_length, core_span=core_span, padded_numel=padded_numel, per_core=per_core, n_tiles=n_tiles)


def make(shapes, interpret=None):
    """Build the jitted kernel for `shapes` (dict: tensor name -> shape)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shapes = {k: tuple(int(x) for x in v) for k, v in shapes.items()}
    # ---- pass 4: flatten + pad the flat span (GM layout) ----
    shapes_flat = {k: (_numel(v),) for k, v in shapes.items()}
    p0 = _plan(shapes_flat)
    padded = dict(shapes_flat)
    padded['param'] = (_rup(shapes_flat['param'][0], p0['core_span']),)
    padded['grad'] = (_rup(shapes_flat['grad'][0], p0['core_span']),)
    padded['m'] = (_rup(shapes_flat['m'][0], p0['core_span']),)
    padded['v'] = (_rup(shapes_flat['v'][0], p0['core_span']),)
    p = _plan(padded)
    param_numel = p['param_numel']
    n_cores = p['n_cores']
    tile_length = p['tile_length']
    core_span = p['core_span']
    padded_numel = p['padded_numel']
    per_core = p['per_core']
    n_tiles = p['n_tiles']
    param_pnumel = _numel(padded['param'])
    grad_pnumel = _numel(padded['grad'])
    m_pnumel = _numel(padded['m'])
    v_pnumel = _numel(padded['v'])

    # ---- pass 2: TQue -> BlockSpec-pipelined VMEM blocks ----
    _in_specs = [
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
    ]
    _out_specs = [
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
        pl.BlockSpec((tile_length,), lambda i, j: ((((((1) * (per_core))) // (tile_length))) * i + (((((1) * (tile_length))) // (tile_length))) * j + (((((((0) * (per_core)) + ((0) * (tile_length))) + ((0) * (((1) * (tile_length)))))) // (tile_length))),)),
    ]
    _out_shape = [
        jax.ShapeDtypeStruct((param_pnumel,), jnp.float32),
        jax.ShapeDtypeStruct((m_pnumel,), jnp.float32),
        jax.ShapeDtypeStruct((v_pnumel,), jnp.float32),
    ]

    def adamw_kernel(_q_0_param_t, _q_1_grad_t, _q_2_m_t, _q_3_v_t, _o_param, _o_m, _o_v):
        # ---- copyin (double-buffered by the Pallas pipeline) ----
        param_t = _q_0_param_t[...]
        grad_t = _q_1_grad_t[...]
        m_t = _q_2_m_t[...]
        v_t = _q_3_v_t[...]
        # ---- compute ----
        new_m1 = (m_t * 0.9)
        t3 = (grad_t * 0.09999999999999998)
        new_m1 = (new_m1 + t3)
        new_v2 = (v_t * 0.999)
        t3 = (grad_t * grad_t)
        t3 = (t3 * 0.0010000000000000009)
        new_v2 = (new_v2 + t3)
        t3 = (new_v2 * 100.45082541138463)
        t3 = jnp.sqrt(t3)
        t3 = (t3 + 1e-08)
        u4 = (new_m1 * 0.0015353399327876296)
        u4 = (u4 / t3)
        wd6 = (param_t * 1e-05)
        u4 = (u4 + wd6)
        new_p5 = (param_t - u4)
        # ---- copyout ----
        _o_param[...] = new_p5
        _o_m[...] = new_m1
        _o_v[...] = new_v2

    _fn = pl.pallas_call(
        adamw_kernel,
        grid=(n_cores, n_tiles,),
        in_specs=_in_specs,
        out_specs=_out_specs,
        out_shape=_out_shape,
        input_output_aliases={0: 0, 2: 1, 3: 2},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel',)),
    )

    def _run(param, grad, m, v):
        _f_param = jnp.asarray(param, jnp.float32)
        _f_param = _f_param.reshape(-1)
        _f_param = _pad_to(_f_param, padded['param'], 0.0)
        _f_grad = jnp.asarray(grad, jnp.float32)
        _f_grad = _f_grad.reshape(-1)
        _f_grad = _pad_to(_f_grad, padded['grad'], 0.0)
        _f_m = jnp.asarray(m, jnp.float32)
        _f_m = _f_m.reshape(-1)
        _f_m = _pad_to(_f_m, padded['m'], 0.0)
        _f_v = jnp.asarray(v, jnp.float32)
        _f_v = _f_v.reshape(-1)
        _f_v = _pad_to(_f_v, padded['v'], 0.0)
        _res = _fn(_f_param, _f_grad, _f_m, _f_v)
        _out_param = _res[0].reshape(-1)[: _numel(shapes['param'])].reshape(shapes['param'])
        _out_m = _res[1].reshape(-1)[: _numel(shapes['m'])].reshape(shapes['m'])
        _out_v = _res[2].reshape(-1)[: _numel(shapes['v'])].reshape(shapes['v'])
        return (_out_param, _out_m, _out_v)
    return jax.jit(_run)


@functools.lru_cache(maxsize=32)
def _cached_make(shape_key, interpret):
    return make(dict(shape_key), interpret=interpret)


def adamw(param, grad, m, v, out_shapes=None, interpret=None):
    """Convenience entry point (builds & caches per shape)."""
    _arrs = [param, grad, m, v]
    shapes = {}
    shapes['param'] = tuple(_arrs[0].shape)
    shapes['grad'] = tuple(_arrs[1].shape)
    shapes['m'] = tuple(_arrs[2].shape)
    shapes['v'] = tuple(_arrs[3].shape)
    _p0 = _plan({k: (_numel(v),) for k, v in shapes.items()})
    key = tuple(sorted(shapes.items()))
    fn = _cached_make(key, interpret)
    return fn(param, grad, m, v)
