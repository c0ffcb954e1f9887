"""latency_p50_ms: 50th percentile over every request of the window of
its scheduled arrival to its label on the host; a request never served
counts as missing (above every served one)."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx["rec"].latency_s, 50)
    return None if v is None else v * 1e3
