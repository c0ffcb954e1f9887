"""Kinds of configuration: everything of a run that differs by kind.

A configuration file names its kind (``"kind": "p2m_vgg"``), and
``run.load_kind`` finds ``bench/kinds/<kind>.py`` by that name. A new kind
is new files: its module here, its plain reference beside it, and
configurations that name it. Under ``bench/`` only kind modules import the
program. A kind module provides:

Server(cfg, seed, trace)    built in set-up from the seed: weights, the
                            input pool and the engine on them (with the
                            program's counters and spans when ``trace``).
                            What the shared loops (``loops.py``) use of it:
    .mb, .pool_n            items per microbatch, inputs in the pool
    .blocks(n)              the pool cut into blocks of ``n`` inputs
    .window(idx)            pool inputs ``idx`` (length mb) as one input
    .serve(x)               serve one item: (outputs on the host, the served
                            result, each microbatch's own outputs, the
                            item's index)
    .samples(i, idx, real, out, parts)
                            the item's ``loops.Sample`` list for the check
    .microbatches           microbatches served so far
  and after the window:
    .counters()             the program's counters (traced runs)
    .step_programs()        the served engine's own step programs as
                            optimized HLO text (traced runs, for
                            ``program_trace``)
readings(cfg, server, seed, samples, control=False)
                            the compared numbers of the seeded sample,
                            from the kind's plain reference (which imports
                            nothing of the program); with ``control`` the
                            control's in the program's place
limits(cfg)                 each compared number's limit
work(cfg)                   the work of one item by part of the model,
                            ``{part: {"flops": .., "bytes": ..}}``, with
                            the whole forward pass under ``model``: what
                            the roofline and MFU readers divide by
KERNELS, SCOPES, SPANS, MARKS
                            what the trace is read by: kernel names, the
                            model's device scopes (a regex whose group is
                            the scope), the engine's host spans outermost
                            first, and {reading: mark name or None}
LOOPS (optional)            {loop name: loop} for traffic whose item the
                            shared ``closed`` and ``open`` loops cannot
                            drive; a loop has their signature
"""
