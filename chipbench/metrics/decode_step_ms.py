"""Device time of one batched decode program execution, median over the
traced window (``cb.decode`` launches)."""
from chipbench.lib.context import median

UNIT = "ms"


def read(run):
    v = median([(x.module.end - x.module.start) / 1e6
                for x in run.of_kind("cb.decode")])
    return v
