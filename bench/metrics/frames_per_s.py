"""frames_per_s: frames whose labels reached the host, over the window."""


def read(ctx):
    rec = ctx["rec"]
    return rec.frames / rec.window_s if rec.window_s > 0 else None
