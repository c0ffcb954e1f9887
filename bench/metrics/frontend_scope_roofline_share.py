"""frontend_scope_roofline_share: the least time the frontend's work of the
traced window could take on this chip (``counts.frontend_min_seconds``, as
``frontend_roofline_share``) over the device time of every operation under
the model's ``p2m_frontend`` scope (``program_trace``): the kernels and
the glue around them, for whichever backend runs, in percent."""
from bench import counts, program_trace


def read(ctx):
    program = program_trace.of_run(ctx, __file__)
    secs = (program or {}).get("scope_s", {}).get("p2m_frontend", 0.0)
    if secs <= 0 or ctx["rec"].frames == 0:
        return None
    least, _ = counts.frontend_min_seconds(ctx["config"], ctx["rec"].frames,
                                           ctx["peak"])
    return 100.0 * least / secs
