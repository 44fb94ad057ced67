"""Device time of one of the model's layers inside a program execution,
where the compiler leaves some of the layer's operations without the
layer's scope.

``jax.named_scope`` reaches an operation's ``tf_op`` stat through the
instruction's ``op_name`` metadata (``host_spans``).  An instruction the
compiler makes in rewriting another can lose it: on a TPU,
``jax.lax.ragged_dot`` becomes Mosaic kernels whose ``tf_op`` is only
their own name (``ragged-dot-none:``).  An architecture module names such
operations, by a fragment of their name, in ``UNSCOPED[<scope>]``.
"""
from __future__ import annotations

from chipbench.lib import host_spans


def unscoped(arch, scope: str) -> tuple[str, ...]:
    """The name fragments of the operations ``arch``'s ``scope`` runs
    without its scope (none for a module that names none)."""
    return tuple(getattr(arch, "UNSCOPED", {}).get(scope, ()))


def layer_ns(sp: host_spans.Spans, module, scope: str,
             fragments: tuple[str, ...] = ()) -> float:
    """Self time of the operations inside one program execution whose
    scope path holds ``scope``, or whose name or scope holds one of
    ``fragments``."""
    part = f"/{scope}/"
    return sum(o.self_ns for o in host_spans.ops_of(sp, module)
               if part in "/" + o.scope
               or any(f in o.name or f in o.scope for f in fragments))
