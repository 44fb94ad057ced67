"""Share of the traced window (the window of ``idle_share.serve``) in which
no program ran on chip 0 while the host was inside a ``serve.admit``
span: the exact intersection of the idle intervals with the admissions."""
from chipbench.lib import host_spans
from chipbench.lib import trace as tr

UNIT = "%"


def read(run):
    t = run.trace
    if t is None or not t.devices or t.t1 <= t.t0:
        return None
    sp = host_spans.load(run.xplane)
    admits = tr.union((s.start, s.end) for s in sp.spans
                      if s.name == "serve.admit")
    if not admits:
        return None
    idle = tr.idle_gaps(t.devices[0], t.t0, t.t1)
    return 100.0 * host_spans.overlap_ns(idle, admits) / (t.t1 - t.t0)
