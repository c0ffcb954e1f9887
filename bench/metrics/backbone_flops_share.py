"""backbone_flops_share: the backbone's FLOPs of the traced window (2 per
MAC of the 3x3 conv stack, ``counts.backbone_macs``, times the frames
served) over the chips' bf16 peak, as a share of the device time of the
operations under the model's ``backbone`` scope (``program_trace``), in
percent. A compute-side floor: the time at peak over the time taken."""
from bench import counts, program_trace


def read(ctx):
    program = program_trace.of_run(ctx, __file__)
    secs = (program or {}).get("scope_s", {}).get("backbone", 0.0)
    if secs <= 0 or ctx["rec"].frames == 0:
        return None
    flops = 2 * counts.backbone_macs(ctx["config"]) * ctx["rec"].frames
    return 100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / secs
