"""vlm.moe_roofline_share: the least time the routed experts' work of the
traced window could take on this chip, over the device time of every
operation under the model's ``lm/moe/experts`` scope, prefill's and the
decode steps' (``decode/lm/moe/experts``), and of the grouped-matmul
kernels (the kind's ``KERNELS``, XLA's ``ragged-dot``: their metadata
keeps no scope), in percent. The work per
request is the kind's ``experts_prefill`` and ``experts_decode``: 2 x 3 x
d x f FLOPs per token-expert assignment, and every routed expert's weights
read once per prefill and once per decode step, divided over the batch;
each phase takes the larger of its compute and memory time
(``stats.least_seconds``). Silent where the program has no such scope."""
from bench import program_trace
from bench.stats import least_seconds

PARTS = ("experts_prefill", "experts_decode")
SCOPES = ("lm/moe/experts", "decode/lm/moe/experts")


def read(ctx):
    program = program_trace.of_run(ctx)
    if program is None:
        return None
    secs = (sum(program["scope_s"].get(s, 0.0) for s in SCOPES)
            + sum(ctx["rec"].traced["kernel_s"].values()))
    work = ctx["kind"].work(ctx["config"])
    if secs <= 0 or ctx["rec"].frames == 0 or any(p not in work
                                                  for p in PARTS):
        return None
    least = sum(least_seconds(work[p], ctx["rec"].frames, ctx["peak"])[0]
                for p in PARTS)
    return 100.0 * least / secs
