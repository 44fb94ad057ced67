"""Host time of one call into the decode program, median over the traced
window: the engine's ``serve.decode`` span, which holds the enqueue of
``jit_serve_decode`` and not its run on the chip."""
from chipbench.lib import host_spans
from chipbench.lib.context import median

UNIT = "ms"


def read(run):
    t = run.trace
    if t is None:
        return None
    sp = host_spans.load(run.xplane)
    return median(host_spans.durations_ms(sp.spans, "serve.decode", t.t0,
                                          t.t1))
