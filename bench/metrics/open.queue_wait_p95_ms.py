"""open.queue_wait_p95_ms: 95th percentile over every request of its
scheduled arrival to the dispatch of its window (admission wait)."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx["rec"].queue_s, 95)
    return None if v is None else v * 1e3
