"""Inter-token latency, 95th percentile over every gap between two
consecutive tokens of one request that ends in the window (host clock).
A refill's prefill stalls every active slot, so it shows here."""
from chipbench.lib.context import percentile

UNIT = "ms"


def read(run):
    v = percentile(run.record["stats"].gaps_s, 95)
    return None if v is None else v * 1e3
