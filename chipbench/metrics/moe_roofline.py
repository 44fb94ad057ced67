"""The MoE layers of prefill against their roofline: over
the traced prefill programs, the least time the chip's peaks allow for
the work the cell's architecture module counts (``prefill_moe`` at each
prompt's length), over the device time of the operations whose scope path
holds ``/moe/`` and of those the module names as the layer's but unscoped
(``lib/layer_ops.py``).  A module without ``prefill_moe`` reads
nothing."""
from chipbench.lib import host_spans, layer_ops, work

UNIT = "%"
# the two readings of one program's start (ProfileData's and the trace
# file's own) agree to well under a microsecond
SAME_NS = 1000.0


def read(run):
    count = getattr(run.record["arch"], "prefill_moe", None)
    launches = run.of_kind("cb.prefill")
    if count is None or not launches:
        return None
    sp = host_spans.load(run.xplane)
    also = layer_ops.unscoped(run.record["arch"], "moe")
    starts = [m.start for m in sp.modules]
    least = spent = 0.0
    for x in launches:
        i = min(range(len(starts)), key=lambda j: abs(starts[j]
                                                    - x.module.start),
                default=None)
        if i is None or abs(starts[i] - x.module.start) > SAME_NS:
            continue
        ns = layer_ops.layer_ns(sp, sp.modules[i], "moe", also)
        if ns <= 0:
            continue
        flops, nbytes = count(run.record["conf"],
                              int(x.span.stats["tokens"]))
        least += work.roofline_s(flops, nbytes, run.peak)[0]
        spent += ns / 1e9
    return 100.0 * least / spent if spent > 0 else None
