"""frontend_roofline_share: the least time the frontend's work of the
traced window could take on this chip over the device time of its kernels
(every ``p2m_kernel_*`` event), in percent. The work is counted from shapes
(``counts``): the signed P2M conv's MACs, and its bytes (frames in,
activations out, draw words) -- the same whichever kernel ran. At these
shapes the memory side bounds it."""
from bench import counts


def read(ctx):
    t = ctx["rec"].traced
    if not t or t["kernel_s"].get("p2m_kernel", 0.0) <= 0:
        return None
    least, _ = counts.frontend_min_seconds(ctx["config"], ctx["rec"].frames,
                                           ctx["peak"])
    return 100.0 * least / t["kernel_s"]["p2m_kernel"]
