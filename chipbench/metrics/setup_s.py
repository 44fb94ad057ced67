"""Process start to window open: imports, weights, compiles or cache
loads, the warm-up requests and, in a serving cell, the ramp that fills
every slot once."""
UNIT = "s"


def read(run):
    return run.record["setup_s"]
