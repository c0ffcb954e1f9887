"""engine.host_syncs_per_microbatch: the waits of the engine's
host thread on the device inside a stream in the traced window (fused-guard
waits, blocking exact steps, drains with probes pending, scheduler reads:
the program's ``host_sync`` marks, one per increment of its
``serving_host_syncs_total``) over the microbatches it served there (its
``microbatch`` spans), by ``program_trace``."""
from bench import program_trace


def read(ctx):
    program = program_trace.of_run(ctx)
    if not program or program["marks"].get("syncs") is None \
            or program["span_n"].get("microbatch", 0) == 0:
        return None
    return program["marks"]["syncs"] / program["span_n"]["microbatch"]
