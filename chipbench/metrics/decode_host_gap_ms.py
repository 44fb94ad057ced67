"""Host round trip between decode steps: device idle from the end of one
decode program to the start of the next, when no prefill ran between
them, median over the traced window."""
from chipbench.lib.context import median

UNIT = "ms"


def read(run):
    gaps, prev = [], None
    for x in run.executions:
        if x.kind == "cb.prefill":
            prev = None
        elif x.kind == "cb.decode":
            if prev is not None:
                gaps.append((x.module.start - prev.module.end) / 1e6)
            prev = x
    return median(gaps)
