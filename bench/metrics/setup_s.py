"""setup_s: process start to the first timed request (imports, weights and
frames made from the seed, compilation or cache loads, warm-up steps)."""


def read(ctx):
    return ctx["rec"].setup_s
