"""Pass 3 — kernel computation translation (paper §4.2).

Translates DSL stage blocks into the Pallas kernel body.  Mirrors the
paper's constraints: each copyin/compute/copyout block becomes a clearly
delimited section of the kernel (comment-fenced in the generated source),
loads/stores cannot interleave with compute inside a stage, and loops become
``jax.lax.fori_loop`` with explicit carries for running scalars and
accumulator buffers.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..dsl import ast as A
from ..codegen.sexpr import emit_sexpr, emit_const
from .analysis import assigned_scalars, written_buffers

JNP_DT = {
    A.DType.f32: "jnp.float32", A.DType.bf16: "jnp.bfloat16",
    A.DType.f16: "jnp.float16", A.DType.i32: "jnp.int32",
    A.DType.b8: "jnp.bool_", A.DType.i8: "jnp.int8",
    A.DType.fp8: "jnp.float8_e4m3fn",
}

# op name -> python expression template; {0},{1},... are operand slots
_UNARY = {
    "exp": "jnp.exp({0})", "log": "jnp.log({0})", "log1p": "jnp.log1p({0})",
    "expm1": "jnp.expm1({0})", "abs": "jnp.abs({0})", "neg": "-({0})",
    "relu": "jnp.maximum({0}, 0)", "sigmoid": "jax.nn.sigmoid({0})",
    "logistic": "jax.nn.sigmoid({0})", "tanh": "jnp.tanh({0})",
    "sqrt": "jnp.sqrt({0})", "rsqrt": "jax.lax.rsqrt({0})",
    "reciprocal": "(1.0 / ({0}))", "erf": "jax.lax.erf({0})",
    "floor": "jnp.floor({0})", "square": "({0} * {0})",
    "softplus": "jax.nn.softplus({0})", "sign": "jnp.sign({0})",
    "gelu": "jax.nn.gelu({0}, approximate=False)",
    "silu": "jax.nn.silu({0})",
    "mish": "({0} * jnp.tanh(jax.nn.softplus({0})))",
    "hardswish": "jax.nn.hard_swish({0})",
    "hardsigmoid": "jax.nn.hard_sigmoid({0})",
    "elu": "jax.nn.elu({0})", "selu": "jax.nn.selu({0})",
    "softsign": "jax.nn.soft_sign({0})", "isnan": "jnp.isnan({0})",
}
_BINARY = {
    "add": "({0} + {1})", "sub": "({0} - {1})", "mul": "({0} * {1})",
    "div": "({0} / {1})", "max": "jnp.maximum({0}, {1})",
    "min": "jnp.minimum({0}, {1})", "pow": "jnp.power({0}, {1})",
    "mod": "jnp.mod({0}, {1})", "atan2": "jnp.arctan2({0}, {1})",
    "lt": "({0} < {1})", "le": "({0} <= {1})", "gt": "({0} > {1})",
    "ge": "({0} >= {1})", "eq": "({0} == {1})", "ne": "({0} != {1})",
}
_REDUCE = {
    "reduce_sum": "jnp.sum", "reduce_max": "jnp.max", "reduce_min": "jnp.min",
    "reduce_prod": "jnp.prod", "reduce_mean": "jnp.mean",
}


class EmitError(Exception):
    pass


class BodyEmitter:
    """Emits the kernel body; tracks defined names and loop carries."""

    def __init__(self, kernel: A.KernelFn, load_emit, store_emit,
                 scalar_dtype: str = "jnp.float32"):
        """load_emit(load, emitter) / store_emit(store, emitter) are backend
        hooks returning source lines (explicit vs pipelined differ only in
        how GM traffic is expressed)."""
        self.kernel = kernel
        self.load_emit = load_emit
        self.store_emit = store_emit
        self.scalar_dtype = scalar_dtype
        self.lines: List[str] = []
        self.indent = 1
        self.defined: List[str] = []         # definition order (buffers+scalars)
        self.buf_dtype: Dict[str, A.DType] = {}
        self.tmp_counter = 0

    # -- plumbing --------------------------------------------------------
    def w(self, line: str = ""):
        self.lines.append("    " * self.indent + line if line else "")

    def fresh(self, stem="_t"):
        self.tmp_counter += 1
        return f"{stem}{self.tmp_counter}"

    def define(self, name: str):
        if name not in self.defined:
            self.defined.append(name)

    # -- entry -------------------------------------------------------------
    def emit_body(self, body: Sequence[A.Stmt]):
        for st in body:
            self.emit_stmt(st)

    def emit_stmt(self, st: A.Stmt):
        if isinstance(st, A.AllocUB):
            b = st.buf
            self.buf_dtype[b.name] = b.dtype
            shape = self._shape_code(b)
            self.w(f"{b.name} = jnp.zeros({shape}, {JNP_DT[b.dtype]})"
                   f"  # UB alloc ({b.nbytes} B -> VMEM)")
            self.define(b.name)
        elif isinstance(st, A.CopyIn):
            self.w("# ---- copyin ----")
            for ld in st.body:
                for line in self.load_emit(ld, self):
                    self.w(line)
                self.buf_dtype[ld.dst.name] = ld.dst.dtype
                self.define(ld.dst.name)
        elif isinstance(st, A.ComputeBlock):
            self.w("# ---- compute ----")
            for op in st.body:
                self.emit_compute(op)
        elif isinstance(st, A.CopyOut):
            self.w("# ---- copyout ----")
            for s in st.body:
                for line in self.store_emit(s, self):
                    self.w(line)
        elif isinstance(st, A.ScalarDecl):
            self.w(f"{st.var.name} = jnp.asarray({emit_sexpr(st.init)}, "
                   f"{self.scalar_dtype})")
            self.define(st.var.name)
        elif isinstance(st, A.ForRange):
            self.emit_loop(st)
        else:
            raise EmitError(f"cannot emit {type(st).__name__}")

    # -- loops -------------------------------------------------------------
    def emit_loop(self, st: A.ForRange):
        carried = [n for n in self.defined
                   if n in assigned_scalars(st.body) | written_buffers(st.body)]
        var = st.var.name
        fn = f"_loop_{var}"
        start = emit_sexpr(st.start)
        count = getattr(st, "count_name", None) or repr(st.count)
        carry_tuple = ", ".join(carried)
        self.w(f"def {fn}({var}, _carry):")
        self.indent += 1
        if carried:
            self.w(f"({carry_tuple},) = _carry")
        saved_defined = list(self.defined)
        self.emit_body(st.body)
        self.defined = saved_defined
        if carried:
            self.w(f"return ({carry_tuple},)")
        else:
            self.w("return _carry")
        self.indent -= 1
        if carried:
            self.w(f"({carry_tuple},) = jax.lax.fori_loop("
                   f"{start}, {start} + {count}, {fn}, ({carry_tuple},))")
        else:
            self.w(f"jax.lax.fori_loop({start}, {start} + {count}, {fn}, 0)")

    # -- compute ops ---------------------------------------------------------
    def emit_compute(self, st: A.Stmt):
        if isinstance(st, A.ScalarDecl):
            self.w(f"{st.var.name} = jnp.asarray({emit_sexpr(st.init)}, "
                   f"{self.scalar_dtype})")
            self.define(st.var.name)
            return
        if isinstance(st, A.ScalarAssign):
            self.w(f"{st.var.name} = jnp.asarray({emit_sexpr(st.expr)}, "
                   f"{self.scalar_dtype})")
            return
        if not isinstance(st, A.Op):
            raise EmitError(f"{type(st).__name__} in compute block")
        self.w(self._op_code(st))
        self.buf_dtype[st.dst.name] = st.dst.dtype
        self.define(st.dst.name)

    def _operand(self, s) -> Tuple[str, Optional[A.DType]]:
        if isinstance(s, A.Buffer):
            return s.name, s.dtype
        return emit_sexpr(s), None

    def _op_code(self, op: A.Op) -> str:
        srcs = [self._operand(s) for s in op.srcs]
        codes = [c for c, _ in srcs]
        dts = [d for _, d in srcs]
        dst = op.dst
        dt = JNP_DT[dst.dtype]
        name = op.op

        def cast_if_needed(expr, force=False):
            src_dts = [d for d in dts if d is not None]
            same = all(d == dst.dtype for d in src_dts) and src_dts
            if force or not same:
                return f"{expr}.astype({dt})"
            return expr

        if name in _UNARY:
            return f"{dst.name} = {cast_if_needed(_UNARY[name].format(*codes))}"
        if name in _BINARY:
            expr = _BINARY[name].format(*codes)
            if name in ("lt", "le", "gt", "ge", "eq", "ne", "isnan"):
                return f"{dst.name} = {expr}.astype({dt})"
            return f"{dst.name} = {cast_if_needed(expr)}"
        if name in _REDUCE:
            axis = op.attrs.get("axis")
            keep = op.attrs.get("keepdims", True)
            expr = (f"{_REDUCE[name]}({codes[0]}, axis={axis!r}, "
                    f"keepdims={keep!r})")
            if A.infer_shape(op) != dst.shape:
                expr += f".reshape({self._shape_code(dst)})"
            return f"{dst.name} = {cast_if_needed(expr, force=True)}"
        if name == "where":
            return (f"{dst.name} = jnp.where({codes[0]}, {codes[1]}, "
                    f"{codes[2]}).astype({dt})")
        if name == "iota":
            axis = op.attrs.get("axis", len(dst.shape) - 1)
            # Mosaic lowers integer iotas only: build int32, then cast
            return (f"{dst.name} = jax.lax.broadcasted_iota(jnp.int32, "
                    f"{self._shape_code(dst)}, {axis}).astype({dt})")
        if name == "full":
            return (f"{dst.name} = jnp.full({self._shape_code(dst)}, "
                    f"{codes[0]}, {dt})")
        if name == "static_slice":
            sl = ", ".join(
                f"slice({a!r}, {b!r}, {c!r})" for (a, b, c) in op.attrs["slices"])
            return f"{dst.name} = {codes[0]}[{sl}]"
        if name == "reshape":
            return f"{dst.name} = {codes[0]}.reshape({self._shape_code(dst)})"
        if name == "transpose":
            return (f"{dst.name} = jnp.transpose({codes[0]}, "
                    f"{tuple(op.attrs['perm'])!r})")
        if name == "cumsum":
            axis = op.attrs.get("axis", -1)
            return f"{dst.name} = {cast_if_needed(f'jnp.cumsum({codes[0]}, axis={axis})', force=True)}"
        if name == "clamp":
            return (f"{dst.name} = jnp.clip({codes[0]}, {codes[1]}, "
                    f"{codes[2]}).astype({dt})")
        if name in ("copy", "cast", "broadcast"):
            return (f"{dst.name} = jnp.broadcast_to({codes[0]}, "
                    f"{self._shape_code(dst)}).astype({dt})")
        if name == "rev":
            axis = op.attrs.get("axis", -1)
            return f"{dst.name} = jnp.flip({codes[0]}, axis={axis})"
        if name == "concat":
            axis = op.attrs.get("axis", 0)
            return (f"{dst.name} = jnp.concatenate(["
                    f"{', '.join(codes)}], axis={axis})")
        if name == "matmul":
            # a may be rank 1 or 2; contract a's last axis with rhs rows
            rhs = f"{codes[1]}.T" if op.attrs.get("transpose_b") else codes[1]
            a_rank = (len(op.srcs[0].shape)
                      if isinstance(op.srcs[0], A.Buffer) else 2)
            expr = (f"jax.lax.dot_general({codes[0]}, {rhs}, "
                    f"((({a_rank - 1},), (0,)), ((), ())), "
                    f"preferred_element_type=jnp.float32)")
            return f"{dst.name} = {cast_if_needed(expr, force=True)}"
        raise EmitError(f"op {name}")

    def _shape_code(self, buf: A.Buffer) -> str:
        names = getattr(buf, "shape_names", None) or (None,) * len(buf.shape)
        parts = [n if n else repr(int(s)) for s, n in zip(buf.shape, names)]
        if len(parts) == 1:
            return f"({parts[0]},)"
        return "(" + ", ".join(parts) + ")"
