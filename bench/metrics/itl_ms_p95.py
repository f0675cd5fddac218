"""95th percentile of the gaps between consecutive tokens of a sequence on
the host, over every decode step in the window (host clock)."""
from bench.harness import percentile


def read(run):
    p = percentile(run["itl_s"], 95)
    return None if p is None else p * 1e3
