"""engine.merge_idle_share: device-idle seconds of the traced window during
which the host was inside the engine's ``merge`` span (the innermost engine
span over the gap, ``program_trace``), over the window, in percent."""
from bench import program_trace


def read(ctx):
    program = program_trace.of_run(ctx)
    if not program or "merge" not in program["span_s"] \
            or program["window_s"] <= 0:
        return None
    return 100.0 * program["idle_s"].get("merge", 0.0) / program["window_s"]
