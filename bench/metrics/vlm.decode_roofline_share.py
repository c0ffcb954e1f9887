"""vlm.decode_roofline_share: the least time the decode steps of the
traced window could take on this chip (the kind's ``decode`` work per
request: every weight a step reads and the latent cache, divided over the
batch, and the steps' FLOPs; ``stats.least_seconds``), over the device
time of the operations under the model's ``decode/`` scopes (each decode
step's layers and head, ``program_trace``), in percent. Silent where the
program has no such scope."""
from bench import program_trace
from bench.stats import least_seconds


def read(ctx):
    program = program_trace.of_run(ctx)
    secs = (program or {}).get("scope_s", {}).get("decode", 0.0)
    work = ctx["kind"].work(ctx["config"]).get("decode")
    if secs <= 0 or ctx["rec"].frames == 0 or work is None:
        return None
    least, _ = least_seconds(work, ctx["rec"].frames, ctx["peak"])
    return 100.0 * least / secs
