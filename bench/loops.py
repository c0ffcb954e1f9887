"""The one traffic generator: closed and open loops over a VisionEngine.

A traffic file (``traffic/<mix>.json``) names its ``loop`` and parameters;
a cell file (``cells/<workload>.json``), where there is one, overrides them
for one cell (the open loop's fixed rate). Both loops feed ONE long-lived
``engine.stream()`` iterable, so the engine's carried threshold persists
across the window as in deployment, and fetch each item's labels to the
host.

closed  one client sends batches of ``batch_microbatches`` microbatches
        back to back: the next when the previous one's labels are on the
        host; frames from a pool made from the seed, in a fixed cycle. The
        engine splits each batch into microbatches and merges their
        outputs into one result.
open    single-frame requests from many cameras arrive on a schedule made
        from the seed (counter-hash Poisson gaps); the harness admits them
        into windows that close at ``window_frames`` frames or
        ``window_ms`` after their first arrival, pads a short window with
        cyclic copies of its own frames (neither counted nor compared) and
        serves it as one stream item. Latency runs from a request's
        scheduled arrival to its label on the host.

Every item records what the correctness check needs of a seeded sample of
items (``Sample``, one per microbatch): the stream index and the
microbatch's place in the item (which fix the engine's rng key), its
frames' pool indices, its own outputs and the rows of the served result
that are its answers.
"""
from __future__ import annotations

import math
import random
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import system

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
    out: Dict                  # its own outputs; "probs" is the served
                               # result of the whole item


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


_KEEP = ("theta", "theta_used", "channel_rates", "stream_fused")


def item_samples(index: int, idx: np.ndarray, real: int, out: Dict,
                 parts: List[Dict]) -> List[Sample]:
    """The samples of one served item: one per microbatch, each with its
    own outputs (``parts``, in order) and the item's served probabilities
    (``out``), of which rows ``row0 ..`` are its answers."""
    mb = len(idx) // len(parts)
    return [Sample(index, j, len(parts), idx[j * mb:(j + 1) * mb],
                   max(0, min(mb, real - j * mb)), j * mb,
                   {"probs": out["probs"],
                    **{k: p[k] for k in _KEEP if k in p}})
            for j, p in enumerate(parts)]


class Feed:
    """The iterable ``engine.stream()`` pulls from: the harness sets the
    next item before asking for its result."""

    def __init__(self):
        self.frames: Optional[jax.Array] = None

    def __iter__(self):
        return self

    def __next__(self):
        frames, self.frames = self.frames, None
        if frames is None:
            raise StopIteration
        return frames


@dataclass
class Record:
    """What one run's window produced, for the metric readers."""
    setup_s: float = 0.0         # process start to the first timed step
    window_s: float = 0.0
    frames: int = 0              # frames whose labels reached the host
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


class Server:
    """The engine behind one stream. The frame pool is kept whole (the
    closed loop cuts it, once, into batch-sized blocks) and as one row per
    frame (what the open loop gathers a window from), so an item adds no
    device work of the harness's own beyond a gather of its own frames.
    ``parts`` receives each microbatch's own outputs as the engine makes
    them (``system.record_microbatches``)."""

    def __init__(self, engine, pool: jax.Array, mb: int):
        self.engine, self.mb, self.pool = engine, mb, pool
        self.pool_n, self.frame_shape = pool.shape[0], pool.shape[1:]
        self.rows = pool.reshape(self.pool_n, -1)
        self.parts = system.record_microbatches(engine)
        self.feed = Feed()
        self.outs = engine.stream(self.feed)
        self.index = 0           # stream items served
        self.microbatches = 0    # microbatches the stream served
        shape = (mb,) + tuple(self.frame_shape)
        self._gather = jax.jit(
            lambda rows, i: jnp.take(rows, i, axis=0).reshape(shape))

    def blocks(self, n: int) -> List[jax.Array]:
        """The pool cut into consecutive blocks of ``n`` frames."""
        return [self.pool[i:i + n] for i in range(0, self.pool_n, n)]

    def serve(self, frames: jax.Array):
        """Serve one stream item; returns (labels on the host, the served
        result, each microbatch's own outputs, stream index)."""
        self.feed.frames = frames
        self.parts.clear()
        out = next(self.outs)
        labels = np.asarray(out["labels"])
        parts = list(self.parts)
        i, self.index = self.index, self.index + 1
        self.microbatches += len(parts)
        return labels, out, parts, i

    def window(self, idx: np.ndarray) -> jax.Array:
        """Pool frames ``idx`` (length mb) as one microbatch."""
        return self._gather(self.rows, jnp.asarray(idx, jnp.int32))


def _finish(rec: Record, server: Server, res: Reservoir) -> None:
    rec.stream_steps = server.microbatches
    rec.samples = [s for item in res.items for s in item]


def closed(server: Server, traffic: Dict, seconds: float, seed: int,
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
        res.offer(lambda: item_samples(i, idx, n, out, parts))
    rec.window_s = now() - t0
    if tracer is not None:
        rec.traced = tracer.stop(rec.window_s)
    rec.attempted = rec.frames
    _finish(rec, server, res)
    return rec


def open_loop(server: Server, traffic: Dict, seconds: float, seed: int,
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
        res.offer(lambda: item_samples(i, idx, take, out, parts))
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
