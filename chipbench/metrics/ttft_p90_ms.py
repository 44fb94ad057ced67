"""Time to first token, 90th percentile over every request sent in the
window: from its send (the completion that freed its client) to its first
token, on the host clock."""
from chipbench.lib.context import percentile

UNIT = "ms"


def read(run):
    v = percentile(run.record["stats"].ttft_s, 90)
    return None if v is None else v * 1e3
