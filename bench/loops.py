"""The one traffic generator: closed and open loops over a kind's server.

A traffic file (``traffic/<mix>.json``) names its ``loop`` and parameters;
a cell file (``cells/<workload>.json``), where there is one, overrides them
for one cell (the open loop's fixed rate). Both loops drive the server of
the configuration's kind (``kinds/``), which serves one item at a time and
brings its outputs to the host.

closed  one client sends batches of ``batch_microbatches`` microbatches
        back to back: the next when the previous one's outputs are on the
        host; inputs from a pool made from the seed, in a fixed cycle.
open    single-input requests from many clients arrive on a schedule made
        from the seed (counter-hash Poisson gaps); the harness admits them
        into windows that close at ``window_frames`` requests or
        ``window_ms`` after their first arrival, pads a short window with
        cyclic copies of its own inputs (neither counted nor compared) and
        serves it as one item. Latency runs from a request's scheduled
        arrival to its output on the host.

Every item offers what the correctness check needs of a seeded sample of
items (``Sample``, one per microbatch): the item's index and the
microbatch's place in it (which fix the engine's rng key), its inputs'
pool indices, its own outputs and the rows of the served result that are
its answers.
"""
from __future__ import annotations

import math
import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

now = time.perf_counter

_MASK32 = 0xFFFFFFFF
_GOLDEN = 0x9E3779B9


def _fmix32(h: int) -> int:
    h &= _MASK32
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & _MASK32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & _MASK32
    h ^= h >> 16
    return h


def hash_u01(seed: int, index: int) -> float:
    """Deterministic uniform in [0, 1) from a (seed, counter) pair; seeds
    past 32 bits fold their high word in."""
    s = _fmix32(seed & _MASK32) ^ ((seed >> 32) & _MASK32)
    h = _fmix32((_fmix32(s) + index * _GOLDEN) & _MASK32)
    return h / 4294967296.0


def arrivals(rate: float, seconds: float, seed: int) -> List[float]:
    """Poisson arrival offsets (s) in [0, seconds) at ``rate`` per second:
    gap i is -ln(1 - u_i) / rate."""
    out, t, i = [], 0.0, 0
    while True:
        t += -math.log(1.0 - hash_u01(seed, i)) / rate
        i += 1
        if t >= seconds:
            return out
        out.append(t)


# warm-up stream items before the window, through the window's programs
WARM_STEPS = 3


@dataclass
class Sample:
    """One served microbatch kept for the correctness check."""
    index: int                 # the stream item it was served in
    part: int                  # its place among the item's microbatches
    parts: int                 # how many microbatches the item had
    frames: np.ndarray         # pool indices of the microbatch's frames
    real: int                  # how many leading frames were requests
    row0: int                  # its first row in the item's served result
    out: Dict                  # what the check reads of its outputs


class Reservoir:
    """A fixed-size uniform sample of a stream of items, drawn from the
    seed (reservoir sampling): the same seed and item count keep the same
    items."""

    def __init__(self, k: int, seed: int):
        self.k, self.rng, self.seen = k, random.Random(seed), 0
        self.items: List = []

    def offer(self, make: Callable[[], object]) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(make())
            return
        j = self.rng.randrange(self.seen)
        if j < self.k:
            self.items[j] = make()


def item_samples(index: int, idx: np.ndarray, real: int,
                 outs: List[Dict]) -> List[Sample]:
    """The samples of one served item: one per microbatch, with the
    outputs of it the check reads (``outs``, in order); microbatch ``j``
    answers rows ``j * mb ..`` of the item's served result."""
    mb = len(idx) // len(outs)
    return [Sample(index, j, len(outs), idx[j * mb:(j + 1) * mb],
                   max(0, min(mb, real - j * mb)), j * mb, out)
            for j, out in enumerate(outs)]


@dataclass
class Record:
    """What one run's window produced, for the metric readers."""
    setup_s: float = 0.0         # process start to the first timed step
    window_s: float = 0.0
    frames: int = 0              # requests whose outputs reached the host
    steps: int = 0               # stream items served in the window
    stream_steps: int = 0        # microbatches the stream served in all
    attempted: int = 0
    failed: int = 0
    latency_s: List[float] = field(default_factory=list)
    queue_s: List[float] = field(default_factory=list)
    service_s: List[float] = field(default_factory=list)
    late_s: List[float] = field(default_factory=list)
    traced: Optional[Dict] = None     # trace reduction of the traced part
    counters: Dict = field(default_factory=dict)
    samples: List[Sample] = field(default_factory=list)


def _finish(rec: Record, server, res: Reservoir) -> None:
    rec.stream_steps = server.microbatches
    rec.samples = [s for item in res.items for s in item]


def closed(server, traffic: Dict, seconds: float, seed: int,
           sample_steps: int, tracer=None, t_origin: float = 0.0) -> Record:
    """Batches of ``batch_microbatches`` microbatches back to back for
    ``seconds``; the pool is cycled in batch-sized blocks."""
    per = traffic["batch_microbatches"]
    n = per * server.mb
    if server.pool_n % n:
        raise ValueError(f"a pool of {server.pool_n} frames does not cut "
                         f"into batches of {n}")
    blocks = server.blocks(n)
    for s in range(WARM_STEPS):
        server.serve(blocks[s % len(blocks)])
    res = Reservoir(max(sample_steps // per, 1), seed)
    rec = Record()
    if tracer is not None:
        tracer.start()
    t0 = now()
    rec.setup_s = t0 - t_origin
    while now() - t0 < seconds:
        b = rec.steps % len(blocks)
        idx = np.arange(b * n, (b + 1) * n)
        labels, out, parts, i = server.serve(blocks[b])
        rec.steps += 1
        rec.frames += int(labels.shape[0])
        res.offer(lambda: server.samples(i, idx, n, out, parts))
    rec.window_s = now() - t0
    if tracer is not None:
        rec.traced = tracer.stop(rec.window_s)
    rec.attempted = rec.frames
    _finish(rec, server, res)
    return rec


def open_loop(server, traffic: Dict, seconds: float, seed: int,
              sample_steps: int, tracer=None, t_origin: float = 0.0
              ) -> Record:
    """Poisson single-frame arrivals at the cell's fixed rate, admitted
    into windows of ``window_frames`` / ``window_ms``."""
    mb, pool_n = server.mb, server.pool_n
    cap = traffic["window_frames"]
    if not 1 <= cap <= mb:
        raise ValueError(f"window_frames {cap} must lie in 1..{mb}, the "
                         "configuration's microbatch")
    wait_s = traffic["window_ms"] / 1e3
    sched = arrivals(traffic["rate_per_s"], seconds, seed)
    n = len(sched)
    frame_of = [int(hash_u01(seed ^ 0x5BD1E995, i) * pool_n)
                for i in range(n)]
    for s in range(WARM_STEPS):
        server.serve(server.window(
            np.arange(s * mb, (s + 1) * mb) % pool_n))
    res = Reservoir(sample_steps, seed)
    rec = Record(attempted=n)
    done = [math.inf] * n
    disp = [math.inf] * n
    nxt = 0          # next arrival not yet admitted
    head = 0         # first admitted request not yet served
    free_at = 0.0    # when the server last became free
    if tracer is not None:
        tracer.start()
    t0 = now()
    rec.setup_s = t0 - t_origin
    while head < n:
        t = now() - t0
        while nxt < n and sched[nxt] <= t:
            nxt += 1
        queued = nxt - head
        if queued == 0:
            _sleep_until(t0 + sched[nxt])
            continue
        close = (sched[head + cap - 1] if queued >= cap
                 else sched[head] + wait_s)
        if queued < cap and t < close:
            until = close if nxt >= n else min(close, sched[nxt])
            _sleep_until(t0 + until)
            continue
        take = min(queued, cap)
        ids = list(range(head, head + take))
        idx = np.array([frame_of[r] for r in ids], np.int64)
        idx = np.resize(idx, mb)            # cyclic copies pad the window
        rec.late_s.append(t - max(close, free_at))
        labels, out, parts, i = server.serve(server.window(idx))
        t_done = now() - t0
        free_at = t_done
        for r in ids:
            disp[r], done[r] = t, t_done
        head += take
        rec.steps += 1
        rec.frames += take
        res.offer(lambda: server.samples(i, idx, take, out, parts))
    rec.window_s = max(now() - t0, seconds)
    if tracer is not None:
        rec.traced = tracer.stop(rec.window_s)
    rec.latency_s = [done[r] - sched[r] for r in range(n)]
    rec.queue_s = [disp[r] - sched[r] for r in range(n)]
    rec.service_s = [done[r] - disp[r] for r in range(n)]
    _finish(rec, server, res)
    return rec


def _sleep_until(t_abs: float) -> None:
    """Wait until ``t_abs`` on ``now()``: sleep most of it, spin the rest
    (a sleep alone overshoots by tens of microseconds)."""
    while True:
        left = t_abs - now()
        if left <= 0:
            return
        if left > 2e-4:
            time.sleep(left - 1e-4)


LOOPS = {"closed": closed, "open": open_loop}
