"""Share of the traced window in which no program ran on the chip."""
from chipbench.lib import trace as tr

UNIT = "%"


def read(run):
    t = run.trace
    if t is None or not t.devices or t.t1 <= t.t0:
        return None
    busy = sum(tr.busy_ns(d, t.t0, t.t1) for d in t.devices) / len(t.devices)
    return 100.0 * (1.0 - busy / (t.t1 - t.t0))
