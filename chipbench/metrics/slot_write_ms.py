"""Host time of one admission's slot write, median over the traced
window: the engine's ``serve.slot_write`` span, the eager copies of the
prefilled cache into its slot of the batch's cache."""
from chipbench.lib import host_spans
from chipbench.lib.context import median

UNIT = "ms"


def read(run):
    t = run.trace
    if t is None:
        return None
    sp = host_spans.load(run.xplane)
    return median(host_spans.durations_ms(sp.spans, "serve.slot_write",
                                          t.t0, t.t1))
