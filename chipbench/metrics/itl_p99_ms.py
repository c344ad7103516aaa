"""99th percentile of every gap between consecutive tokens of a request,
pooled over all requests, for gaps that end in the window."""
from harness import loop


def read(run):
    p = loop.percentile(loop.gaps_in(run.win), 99)
    return None if p is None else p * 1e3
