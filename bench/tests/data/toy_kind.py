"""Kind ``toy``: a two-layer classifier, brought to the harness as new
files only (the tests copy this module into a checkout's ``bench/kinds/``
beside a configuration that names it).

The program is a jitted two-layer perceptron on the device, in float32;
its plain reference the same equations in float64 NumPy on the host, from
the same weights and inputs, which this module makes from the seed. The
one compared number is the widest gap between a served logit and the
reference's. It brings a loop of its own, ``toy_closed``: the shared
closed loop, announced on standard error.
"""
from __future__ import annotations

import re
import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import loops

POOL_BLOCKS = 8
KERNELS = ()
SCOPES = re.compile(r"(?:^|/)(hidden|head)(?=/|$)")
SPANS = ()
MARKS = {}


def _key(seed: int, i: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF), i)


def weights(cfg: Dict, seed: int) -> Dict:
    k1, k2 = jax.random.split(_key(seed, 1))
    d, h, c = cfg["inputs"], cfg["hidden"], cfg["classes"]
    return {"w1": jax.random.normal(k1, (d, h)) / d ** 0.5,
            "b1": jnp.full((h,), 0.1),
            "w2": jax.random.normal(k2, (h, c)) / h ** 0.5,
            "b2": jnp.zeros((c,))}


@jax.jit
def forward(params, x):
    with jax.named_scope("hidden"):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
    with jax.named_scope("head"):
        return h @ params["w2"] + params["b2"]


class Server:
    """The classifier behind the shared loops: one program call per item,
    whose microbatches are its consecutive blocks of ``microbatch`` rows."""

    def __init__(self, cfg: Dict, seed: int, trace: bool = False):
        self.cfg, self.mb = cfg, cfg["microbatch"]
        self.params = weights(cfg, seed)
        self.pool = jax.random.uniform(
            _key(seed, 2), (POOL_BLOCKS * self.mb, cfg["inputs"]))
        self.pool_n = self.pool.shape[0]
        self.index = self.microbatches = 0

    def blocks(self, n: int) -> List[jax.Array]:
        return [self.pool[i:i + n] for i in range(0, self.pool_n, n)]

    def window(self, idx: np.ndarray) -> jax.Array:
        return jnp.take(self.pool, jnp.asarray(idx, jnp.int32), axis=0)

    def serve(self, x: jax.Array):
        logits = np.asarray(forward(self.params, x))
        parts = [{"logits": logits}] * (x.shape[0] // self.mb)
        i, self.index = self.index, self.index + 1
        self.microbatches += len(parts)
        return logits, {"logits": logits}, parts, i

    def samples(self, i, idx, real, out, parts):
        return loops.item_samples(i, idx, real, parts)

    def counters(self) -> Dict:
        return {}

    def step_programs(self) -> List[str]:
        x = jax.ShapeDtypeStruct((self.mb, self.cfg["inputs"]), jnp.float32)
        return [forward.lower(self.params, x).compile().as_text()]


def _reference(params: Dict, x: np.ndarray, dtype) -> np.ndarray:
    p = {k: np.asarray(v, np.float64).astype(dtype) for k, v in
         params.items()}
    h = np.maximum(x.astype(dtype) @ p["w1"] + p["b1"], 0)
    return (h @ p["w2"] + p["b2"]).astype(np.float64)


def readings(cfg: Dict, server: Server, seed: int, samples,
             control: bool = False) -> Dict[str, float]:
    """The widest gap of a served logit from the float64 reference's; the
    control is the reference in float16 in the program's place."""
    pool = np.asarray(server.pool, np.float64)
    gap = 0.0
    for s in samples:
        ref = _reference(server.params, pool[s.frames], np.float64)
        rows = slice(s.row0, s.row0 + s.real)
        got = (_reference(server.params, pool[s.frames], np.float16)
               if control else s.out["logits"][rows])
        gap = max(gap, float(np.max(np.abs(got[:s.real] - ref[:s.real]))))
    return {"logit_gap": gap}


def limits(cfg: Dict) -> Dict[str, float]:
    return dict(cfg["limits"])


def work(cfg: Dict) -> Dict[str, Dict[str, int]]:
    d, h, c = cfg["inputs"], cfg["hidden"], cfg["classes"]
    return {"model": {"flops": 2 * (d * h + h * c)}}


def toy_closed(server, traffic, seconds, seed, sample_steps, tracer=None,
               t_origin=0.0):
    print("toy loop: closed", file=sys.stderr)
    return loops.closed(server, traffic, seconds, seed, sample_steps,
                        tracer=tracer, t_origin=t_origin)


LOOPS = {"toy_closed": toy_closed}
