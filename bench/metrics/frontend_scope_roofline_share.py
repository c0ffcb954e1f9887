"""frontend_scope_roofline_share: the least time the frontend's work of the
traced window could take on this chip (``stats.least_seconds`` of the
kind's ``p2m_frontend`` work, as ``frontend_roofline_share``) over the
device time of every operation under the model's ``p2m_frontend`` scope
(``program_trace``): the kernels and the glue around them, for whichever
backend runs, in percent."""
from bench import program_trace
from bench.stats import least_seconds


def read(ctx):
    program = program_trace.of_run(ctx)
    secs = (program or {}).get("scope_s", {}).get("p2m_frontend", 0.0)
    work = ctx["kind"].work(ctx["config"]).get("p2m_frontend")
    if secs <= 0 or ctx["rec"].frames == 0 or work is None:
        return None
    least, _ = least_seconds(work, ctx["rec"].frames, ctx["peak"])
    return 100.0 * least / secs
