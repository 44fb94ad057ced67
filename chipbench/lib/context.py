"""What a metric reader reads: one run's record, the chip's peaks, and the
reduced trace of a traced run."""
from __future__ import annotations

from functools import cached_property

import numpy as np

from . import trace as tr


class RunContext:
    def __init__(self, record: dict, peak: dict, xplane=None):
        self.record = record
        self.peak = peak
        self.xplane = xplane

    @cached_property
    def trace(self) -> tr.Trace | None:
        return None if self.xplane is None else tr.load(self.xplane)

    @cached_property
    def executions(self) -> list[tr.Execution]:
        """Program executions on the first chip (empty without a trace)."""
        if self.trace is None or not self.trace.devices:
            return []
        return tr.executions(self.trace, self.trace.devices[0])

    def of_kind(self, kind: str) -> list[tr.Execution]:
        return [x for x in self.executions if x.kind == kind]


def percentile(values, q: float) -> float | None:
    """The q-th percentile (numpy's linear rule), or None without values."""
    return float(np.percentile(values, q)) if len(values) else None


def median(values) -> float | None:
    return percentile(values, 50)
