"""frontend_roofline_share: the least time the frontend's work of the
traced window could take on this chip over the device time of its kernels
(every ``p2m_kernel_*`` event), in percent. The work is the kind's work per
item for its ``p2m_frontend``, counted from shapes: the signed P2M conv's
FLOPs, and its bytes (frames in, activations out, draw words) -- the same
whichever kernel ran. At these shapes the memory side bounds it."""
from bench.stats import least_seconds


def read(ctx):
    t = ctx["rec"].traced
    work = ctx["kind"].work(ctx["config"]).get("p2m_frontend")
    if not t or t["kernel_s"].get("p2m_kernel", 0.0) <= 0 or work is None:
        return None
    least, _ = least_seconds(work, ctx["rec"].frames, ctx["peak"])
    return 100.0 * least / t["kernel_s"]["p2m_kernel"]
