"""90th percentile, over every sequence of the batches begun in the window
whose first token reached the host, of the time from its batch's start to
that token (host clock). The rows of a batch start and finish their prefill
together, so each has its batch's time."""
from bench.harness import percentile


def read(run):
    p = percentile(run["ttft_s"], 90)
    return None if p is None else p * 1e3
