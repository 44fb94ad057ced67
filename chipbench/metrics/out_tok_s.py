"""Output tokens handed to requests in the window, over the window."""
UNIT = "tokens/s"


def read(run):
    s = run.record["stats"]
    return s.out_tokens / s.window_s
