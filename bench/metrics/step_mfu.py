"""step_mfu: model FLOPs per frame (``counts.model_flops_per_frame``:
frontend + backbone + head, 2 per MAC) times the traced window's frames per
second, over the chips' bf16 peak, in percent. The backbone's f32 convs
run with bf16 operands (the TPU's default precision), so bf16 is the peak
they could reach."""
from bench import counts


def read(ctx):
    rec = ctx["rec"]
    if rec.window_s <= 0 or rec.frames == 0:
        return None
    flops = counts.model_flops_per_frame(ctx["config"]) * rec.frames
    return 100.0 * flops / rec.window_s / (
        ctx["peak"]["bf16_flops_per_s"] * ctx["chips"])
