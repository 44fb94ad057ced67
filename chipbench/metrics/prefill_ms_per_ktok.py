"""Device time of a prefill program execution per 1000 prompt tokens,
median over the traced window (``cb.prefill`` launches, whose span
carries the prompt length)."""
from chipbench.lib.context import median

UNIT = "ms/ktok"


def read(run):
    return median([(x.module.end - x.module.start) / 1e6
                   / (int(x.span.stats["tokens"]) / 1e3)
                   for x in run.of_kind("cb.prefill")])
