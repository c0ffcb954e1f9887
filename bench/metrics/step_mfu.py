"""step_mfu: the model's FLOPs per item (the kind's work per item for the
whole forward pass, ``model``: for ``p2m_vgg`` frontend + backbone + head,
2 per MAC) times the traced window's items per second, over the chips'
bf16 peak, in percent. The backbone's f32 convs run with bf16 operands
(the TPU's default precision), so bf16 is the peak they could reach."""


def read(ctx):
    rec = ctx["rec"]
    work = ctx["kind"].work(ctx["config"]).get("model")
    if rec.window_s <= 0 or rec.frames == 0 or work is None:
        return None
    flops = work["flops"] * rec.frames
    return 100.0 * flops / rec.window_s / (
        ctx["peak"]["bf16_flops_per_s"] * ctx["chips"])
