"""open.service_p95_ms: 95th percentile over every request of the
dispatch of its window to its label on the host (the engine step)."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx["rec"].service_s, 95)
    return None if v is None else v * 1e3
