"""Causal prefill attention against its roofline: the work attention
needs at each traced prefill's prompt length (counted by the cell's
architecture module), as the least time the chip's peaks allow, over the
device time of the Mosaic kernels in those prefill programs, which on
this path are the generated flash chain's calls.  Bound by operations at
these lengths."""
from chipbench.lib import trace as tr
from chipbench.lib import work

UNIT = "%"


def read(run):
    arch, conf = run.record["arch"], run.record["conf"]
    least = spent = 0.0
    for x in run.of_kind("cb.prefill"):
        kernel = sum(o.end - o.start for o in x.ops if tr.MOSAIC in o.name)
        if kernel <= 0:
            continue
        flops, nbytes = arch.prefill_attention(
            conf, int(x.span.stats["tokens"]))
        least += work.roofline_s(flops, nbytes, run.peak)[0]
        spent += kernel / 1e9
    return 100.0 * least / spent if spent > 0 else None
