"""Device time of the MoE layers in one decode step, median
over the ``jit_serve_decode`` executions in the traced window: the self
time of the operations whose scope path holds ``/moe/`` (the model's
``jax.named_scope`` around ``models/layers.apply_moe``, its router and
every MLP it routes to), and of those the architecture module names as
the layer's but unscoped (``lib/layer_ops.py``)."""
from chipbench.lib import host_spans, layer_ops
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
    if not any("/moe/" in "/" + o.scope for o in sp.ops):
        return None
    also = layer_ops.unscoped(run.record.get("arch"), "moe")
    return median([layer_ops.layer_ns(sp, m, "moe", also) / 1e6
                   for m in steps])
