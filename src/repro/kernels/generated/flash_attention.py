"""
flash_attention_fused — generated by repro.core (AscendCraft-style DSL-guided transcompilation, codegen v3).

category : fused
strategy : fused streaming chain (tile loops jammed, running scalars loop-carried, links spilled at most once): flash_attention_s0_matmul_t -> flash_attention_s1_scale -> flash_attention_s2_add -> flash_attention_s3_softmax -> flash_attention_s4_matmul
backend  : explicit  (explicit CopyIn/Compute/CopyOut on pl.ANY refs)

Host plan (Pass 1) for the generation shapes {'k': (786432, 128), 'q': (256, 128), 'mask': (256, 786432), 'output': (256, 128), 'v': (786432, 128), 'scratch0': (256, 786432)}:
  q_numel = 32768
  q_dim1 = 128
  rows = 256
  n_cores = 32
  rows_per_core = 8
  tile_length = 4096
  n_tiles_786432 = 192

Transcompilation pass log:
  - pass0/validate: ok (0 warnings)
  - pass2/init: backend=explicit; TQue(in)=['f0_k_t', 'f0_q_t', 'f2_mask_t', 'f3_xt', 'f4_v_t'] TQue(out)=['f2_y1', 'f4_mm_acc'] TBuf=['f0_mm_t', 'f1_y1', 'f3_ea', 'f3_padidx', 'f3_padmsk', 'f3_padnu', 'f3_red', 'f3_yt', 'f4_mm_part']
  - pass4/align: GM layout padded for ['k', 'mask', 'output', 'q', 'scratch0', 'v']
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
    q_numel = (shapes['q'][0] * shapes['q'][1])
    q_dim1 = shapes['q'][1]
    rows = 256
    n_cores = 32  # largest core count dividing rows exactly
    rows_per_core = 8
    tile_length = 4096  # chain-wide column tile: shared by every stage so the loop-carry stitcher can jam identical tile loops
    n_tiles_786432 = 192
    return dict(q_numel=q_numel, q_dim1=q_dim1, rows=rows, n_cores=n_cores, rows_per_core=rows_per_core, tile_length=tile_length, n_tiles_786432=n_tiles_786432)


def make(shapes, interpret=None):
    """Build the jitted kernel for `shapes` (dict: tensor name -> shape)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shapes = {k: tuple(int(x) for x in v) for k, v in shapes.items()}
    p0 = _plan(shapes)
    # ---- pass 4: alignment & padding refinement (GM layout) ----
    padded = dict(shapes)
    _s = list(shapes['q']); _s[-1] = _rup(_s[-1], 128)
    padded['q'] = tuple(_s)
    _s = list(shapes['k']); _s[-1] = _rup(_s[-1], 128)
    padded['k'] = tuple(_s)
    _s = list(shapes['mask']); _s[-1] = _rup(_s[-1], p0['tile_length'])
    padded['mask'] = tuple(_s)
    _s = list(shapes['v']); _s[-1] = _rup(_s[-1], 128)
    padded['v'] = tuple(_s)
    _s = list(shapes['output']); _s[-1] = _rup(_s[-1], 128)
    padded['output'] = tuple(_s)
    _s = list(shapes['scratch0']); _s[-1] = _rup(_s[-1], p0['tile_length'])
    padded['scratch0'] = tuple(_s)
    p = _plan(padded)
    if not (p['rows'] % p['n_cores'] == 0):
        raise ValueError('rows must divide the generated core/block partition; regenerate the chain for this shape')
    if not (shapes['q'][-1] == 64):
        raise ValueError('chain was specialized for a different trailing dimension; regenerate for this shape')
    if not (_numel(shapes['q']) // shapes['q'][-1] == 256):
        raise ValueError('chain was specialized for a different row count; regenerate for this shape')
    if not (tuple(shapes['k']) == (786432, 64)):
        raise ValueError('chain was specialized for k shape (786432, 64); regenerate for this shape')
    if not (tuple(shapes['mask']) == (256, 786432)):
        raise ValueError('chain was specialized for mask shape (256, 786432); regenerate for this shape')
    if not (tuple(shapes['v']) == (786432, 64)):
        raise ValueError('chain was specialized for v shape (786432, 64); regenerate for this shape')
    q_numel = p['q_numel']
    q_dim1 = p['q_dim1']
    rows = p['rows']
    n_cores = p['n_cores']
    rows_per_core = p['rows_per_core']
    tile_length = p['tile_length']
    n_tiles_786432 = p['n_tiles_786432']
    q_pnumel = _numel(padded['q'])
    k_pnumel = _numel(padded['k'])
    mask_pnumel = _numel(padded['mask'])
    v_pnumel = _numel(padded['v'])
    output_pnumel = _numel(padded['output'])
    scratch0_pnumel = _numel(padded['scratch0'])

    _GUARD = 0  # tail guard for masked transfers

    def flash_attention_fused_kernel(q_ref, k_ref, mask_ref, v_ref, output_ref, scratch0_ref):
        pid0 = pl.program_id(0)
        f0_q_t = jnp.zeros((q_dim1,), jnp.float32)  # UB alloc (512 B -> VMEM)
        f0_k_t = jnp.zeros((tile_length, 128), jnp.float32)  # UB alloc (2097152 B -> VMEM)
        f0_mm_t = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f1_y1 = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f2_mask_t = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f2_y1 = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f3_xt = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f3_yt = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f3_red = jnp.zeros((1,), jnp.float32)  # UB alloc (4 B -> VMEM)
        f3_ea = jnp.zeros((1,), jnp.float32)  # UB alloc (4 B -> VMEM)
        f3_padidx = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f3_padmsk = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f3_padnu = jnp.zeros((tile_length,), jnp.float32)  # UB alloc (16384 B -> VMEM)
        f4_v_t = jnp.zeros((tile_length, 128), jnp.float32)  # UB alloc (2097152 B -> VMEM)
        f4_mm_part = jnp.zeros((128,), jnp.float32)  # UB alloc (512 B -> VMEM)
        f4_mm_acc = jnp.zeros((128,), jnp.float32)  # UB alloc (512 B -> VMEM)
        def _loop_row(row, _carry):
            (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_xt, f3_yt, f3_red, f3_ea, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,) = _carry
            f3_row_max = jnp.asarray(-3e+38, jnp.float32)
            f3_row_den = jnp.asarray(0.0, jnp.float32)
            def _loop_f3_t1(f3_t1, _carry):
                (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_yt, f3_red, f3_ea, f3_row_max, f3_row_den,) = _carry
                # ---- copyin ----
                f0_q_t = q_ref[pl.dslice(row * q_dim1, q_dim1)]
                f0_k_t = k_ref[pl.dslice(f3_t1 * 524288, tile_length * 128)].reshape((tile_length, 128))
                f2_mask_t = mask_ref[pl.dslice(row * 786432 + f3_t1 * tile_length, tile_length)]
                # ---- compute ----
                f0_mm_t = jax.lax.dot_general(f0_q_t, f0_k_t.T, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32).astype(jnp.float32)
                f1_y1 = (f0_mm_t * 0.25)
                f2_y1 = (f1_y1 + f2_mask_t)
                f3_red = jnp.max(f2_y1, axis=None, keepdims=True).astype(jnp.float32)
                f3_ea = jnp.full((1,), f3_row_max - jnp.maximum(f3_row_max, f3_red.reshape(-1)[0]), jnp.float32)
                f3_ea = jnp.exp(f3_ea)
                f3_yt = (f2_y1 - jnp.maximum(f3_row_max, f3_red.reshape(-1)[0]))
                f3_yt = jnp.exp(f3_yt)
                f3_row_max = jnp.asarray(jnp.maximum(f3_row_max, f3_red.reshape(-1)[0]), jnp.float32)
                f3_red = jnp.sum(f3_yt, axis=None, keepdims=True).astype(jnp.float32)
                f3_row_den = jnp.asarray(f3_row_den * f3_ea.reshape(-1)[0] + f3_red.reshape(-1)[0], jnp.float32)
                # ---- copyout ----
                scratch0_ref[pl.dslice(row * 786432 + f3_t1 * tile_length, tile_length)] = f2_y1
                return (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_yt, f3_red, f3_ea, f3_row_max, f3_row_den,)
            (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_yt, f3_red, f3_ea, f3_row_max, f3_row_den,) = jax.lax.fori_loop(0, 0 + n_tiles_786432, _loop_f3_t1, (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_yt, f3_red, f3_ea, f3_row_max, f3_row_den,))
            # ---- compute ----
            f4_mm_acc = jnp.full((128,), 0.0, jnp.float32)
            def _loop_f3_t2(f3_t2, _carry):
                (f3_xt, f3_yt, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,) = _carry
                # ---- copyin ----
                f3_xt = scratch0_ref[pl.dslice(row * 786432 + f3_t2 * tile_length, tile_length)]
                f4_v_t = v_ref[pl.dslice(f3_t2 * 524288, tile_length * 128)].reshape((tile_length, 128))
                # ---- compute ----
                f3_yt = (f3_xt - f3_row_max)
                f3_yt = jnp.exp(f3_yt)
                f3_yt = (f3_yt / f3_row_den)
                f3_padidx = jax.lax.broadcasted_iota(jnp.int32, (tile_length,), 0).astype(jnp.float32)
                f3_padidx = (f3_padidx + f3_t2 * tile_length)
                f3_padmsk = (f3_padidx < 786432.0).astype(jnp.float32)
                f3_padnu = jnp.full((tile_length,), 0.0, jnp.float32)
                f3_yt = jnp.where(f3_padmsk, f3_yt, f3_padnu).astype(jnp.float32)
                f4_mm_part = jax.lax.dot_general(f3_yt, f4_v_t, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32).astype(jnp.float32)
                f4_mm_acc = (f4_mm_acc + f4_mm_part)
                return (f3_xt, f3_yt, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,)
            (f3_xt, f3_yt, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,) = jax.lax.fori_loop(0, 0 + n_tiles_786432, _loop_f3_t2, (f3_xt, f3_yt, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,))
            # ---- copyout ----
            output_ref[pl.dslice(row * q_dim1, 128)] = f4_mm_acc
            return (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_xt, f3_yt, f3_red, f3_ea, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,)
        (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_xt, f3_yt, f3_red, f3_ea, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,) = jax.lax.fori_loop(pid0 * rows_per_core, pid0 * rows_per_core + rows_per_core, _loop_row, (f0_q_t, f0_k_t, f0_mm_t, f1_y1, f2_mask_t, f2_y1, f3_xt, f3_yt, f3_red, f3_ea, f3_padidx, f3_padmsk, f3_padnu, f4_v_t, f4_mm_part, f4_mm_acc,))

    _out_shape = [
        jax.ShapeDtypeStruct((output_pnumel + _GUARD,), jnp.float32),
        jax.ShapeDtypeStruct((scratch0_pnumel + _GUARD,), jnp.float32),
    ]
    _fn = pl.pallas_call(
        flash_attention_fused_kernel,
        grid=(n_cores,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=_out_shape,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )

    def _run(q, k, mask, v):
        _f_q = jnp.asarray(q, jnp.float32)
        _f_q = _pad_to(_f_q, padded['q'], 0.0)
        _f_q = _f_q.reshape(-1)
        if _GUARD:
            _f_q = jnp.pad(_f_q, (0, _GUARD), constant_values=0.0)
        _f_k = jnp.asarray(k, jnp.float32)
        _f_k = _pad_to(_f_k, padded['k'], 0.0)
        _f_k = _f_k.reshape(-1)
        if _GUARD:
            _f_k = jnp.pad(_f_k, (0, _GUARD), constant_values=0.0)
        _f_mask = jnp.asarray(mask, jnp.float32)
        _f_mask = _pad_to(_f_mask, padded['mask'], -3e+38)
        _f_mask = _f_mask.reshape(-1)
        if _GUARD:
            _f_mask = jnp.pad(_f_mask, (0, _GUARD), constant_values=-3e+38)
        _f_v = jnp.asarray(v, jnp.float32)
        _f_v = _pad_to(_f_v, padded['v'], 0.0)
        _f_v = _f_v.reshape(-1)
        if _GUARD:
            _f_v = jnp.pad(_f_v, (0, _GUARD), constant_values=0.0)
        _res = _fn(_f_q, _f_k, _f_mask, _f_v)
        _out_output = _unpad(_res[0].reshape(-1), padded['output'], shapes['output'])
        return _out_output
    return jax.jit(_run)


@functools.lru_cache(maxsize=32)
def _cached_make(shape_key, interpret):
    return make(dict(shape_key), interpret=interpret)


def flash_attention_fused(q, k, mask, v, out_shapes=None, interpret=None):
    """Convenience entry point (builds & caches per shape)."""
    _arrs = [q, k, mask, v]
    shapes = {}
    shapes['q'] = tuple(_arrs[0].shape)
    shapes['k'] = tuple(_arrs[1].shape)
    shapes['mask'] = tuple(_arrs[2].shape)
    shapes['v'] = tuple(_arrs[3].shape)
    _p0 = _plan(shapes)
    shapes['output'] = tuple((out_shapes or {}).get('output', tuple(_arrs[0].shape)))
    shapes['scratch0'] = tuple((out_shapes or {}).get('scratch0', (256, 786432)))
    key = tuple(sorted(shapes.items()))
    fn = _cached_make(key, interpret)
    return fn(q, k, mask, v)
