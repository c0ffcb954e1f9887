"""window_compiles: programs JAX loaded (compiled, or read
from the persistent cache) inside the traced window: the program's
``jax_compile`` marks in the window's trace, one per increment of its
``jax_compiles_total`` (``program_trace``)."""
from bench import program_trace


def read(ctx):
    program = program_trace.of_run(ctx)
    return program["marks"].get("compiles") if program else None
