"""device_idle_share: 1 minus the union of the device's operation
intervals in the profiler trace over the traced window, in percent."""


def read(ctx):
    t = ctx["rec"].traced
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
