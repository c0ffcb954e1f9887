"""fused_step_share: microbatches served by the fused single-kernel step
(the program's ``serving_fused_steps_total`` less the drift guard's
``serving_fused_fallback_total``, which re-ran on the exact path) over all
microbatches the stream served, in percent."""


def read(ctx):
    rec = ctx["rec"]
    fused = rec.counters.get("serving_fused_steps_total")
    if fused is None or rec.stream_steps == 0:
        return None
    back = rec.counters.get("serving_fused_fallback_total") or 0.0
    return 100.0 * (fused - back) / rec.stream_steps
