"""Device time of attention in one decode step, median over the
``jit_serve_decode`` executions in the traced window: the self time of the
operations whose scope path holds ``/attention/`` (the model's
``jax.named_scope`` around ``models/layers.apply_attention``; the int8
cache's dequantisation is among them)."""
from chipbench.lib import host_spans
from chipbench.lib.context import median

UNIT = "ms"


def read(run):
    t = run.trace
    if t is None:
        return None
    sp = host_spans.load(run.xplane)
    steps = [m for m in sp.modules
             if m.name.startswith(host_spans.DECODE_PROGRAM)
             and m.start >= t.t0 and m.end <= t.t1]
    if not any(o.scope for o in sp.ops):
        return None
    return median([host_spans.scope_ns(sp, m, "attention") / 1e6
                   for m in steps])
