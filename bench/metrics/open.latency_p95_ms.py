"""open.latency_p95_ms: 95th percentile over every request of the window of
its scheduled arrival to its label on the host; a request never served
counts as missing (above every served one). Read per layer: a host stall
of a second or more leaves a backlog that lifts it a hundredfold in some
runs, too unsteady for a bound end to end."""
from bench.stats import percentile


def read(ctx):
    v = percentile(ctx["rec"].latency_s, 95)
    return None if v is None else v * 1e3
