"""backbone_flops_share: the backbone's FLOPs of the traced window (the
kind's work per item for its ``backbone``: for ``p2m_vgg`` 2 per MAC of
the 3x3 conv stack) times the items served, over the chips' bf16 peak, as
a share of the device time of the operations under the model's
``backbone`` scope (``program_trace``), in percent. A compute-side floor:
the time at peak over the time taken."""
from bench import program_trace


def read(ctx):
    program = program_trace.of_run(ctx)
    secs = (program or {}).get("scope_s", {}).get("backbone", 0.0)
    work = ctx["kind"].work(ctx["config"]).get("backbone")
    if secs <= 0 or ctx["rec"].frames == 0 or work is None:
        return None
    flops = work["flops"] * ctx["rec"].frames
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / secs
