"""Tile autotuner for the P2M frontend kernels (DESIGN.md §9).

The frontend's execution shape is fixed per deployment — one sensor
geometry, one serving batch — so tile selection is a per-shape table, not a
per-call search:

  * ``TileChoice(block_n, block_n_elem, fused)`` — the kernel-A patch-row
    block target, the kernel-B elementwise row-block cap, and whether the
    fused single-kernel streaming path beats the two-kernel pipeline for
    this shape.
  * an IN-PROCESS table keyed by ``(N, K, C)`` = (patch rows, k*k*C_in,
    C_out). ``resolve`` is the only consumer-facing read: explicit caller
    values win, then a tuned/loaded entry, then the deterministic heuristic
    default — and whatever it returns is recorded, so the same shape always
    resolves to the same tiles for the life of the process (a jitted caller
    can never see two different blockings for one shape, which is what
    keeps the jit cache at one entry per shape).
  * ``autotune_frontend`` — the actual search: times ``ops.p2m_frontend``
    (and the fused streaming step) over a deterministic candidate grid and
    stores the winner. Timing is the ONLY nondeterministic ingredient, and
    it is quarantined here: nothing in the serving/test path ever triggers
    a measurement implicitly.
  * ``save_table`` / ``load_table`` — JSON persistence, so a deployment
    tunes once (e.g. in ``benchmarks/frontend_bench.py``, which reports the
    search) and ships the table.

Heuristic default: the largest whole-row block that keeps a single MXU pass
per step without collapsing the grid to one step (``block_n = min(n // 2,
4096)``), and a whole-N target for the fused kernel. Every choice is a
TARGET: the kernels cap each grid step by their VMEM budget
(``blocking.implicit_block`` / ``elem_rows_cap``), so no table entry can
ask the TPU compiler for more scoped VMEM than it grants.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Callable, Dict, Iterable, Optional, Tuple

from repro.obs import clock

TuneKey = Tuple[int, int, int]


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """One tuned configuration for one frontend shape.

    ``block_n`` tiles the EXACT path's kernel A; the fused streaming kernel
    has its own ``block_n_fused`` because its constraints differ — the exact
    path wants >= 2 grid steps (each step's matmul stays at or below the
    ideal-conv flop count), while the fused kernel has no such pressure:
    its default target is the whole microbatch, which the kernel's VMEM
    cap then splits into the largest steps that fit.
    """
    block_n: int          # kernel-A patch-row block target (implicit im2col)
    block_n_elem: int     # kernel-B elementwise row-block cap
    block_n_fused: int = 0  # fused-kernel patch-row target (0 = whole N)
    fused: bool = True    # stream with the single fused kernel
    precision: str = "f32"  # matmul precision the tuner picked (f32 | int8)

    def to_json(self) -> Dict:
        return {"block_n": self.block_n, "block_n_elem": self.block_n_elem,
                "block_n_fused": self.block_n_fused, "fused": self.fused,
                "precision": self.precision}

    @staticmethod
    def from_json(d: Dict) -> "TileChoice":
        return TileChoice(block_n=int(d["block_n"]),
                          block_n_elem=int(d["block_n_elem"]),
                          block_n_fused=int(d.get("block_n_fused", 0)),
                          fused=bool(d["fused"]),
                          precision=str(d.get("precision", "f32")))


_TABLE: Dict[TuneKey, TileChoice] = {}


def shape_key(n: int, k_eff: int, c_out: int) -> TuneKey:
    """Table key: (patch rows N, contraction K = k*k*C_in, C_out)."""
    return (int(n), int(k_eff), int(c_out))


def default_choice(n: int, k_eff: int, c_out: int) -> TileChoice:
    """Deterministic heuristic used when a shape has never been tuned.

    ``block_n = n // 2`` keeps the exact path's kernel A at >= 2 grid steps
    (per-step matmul flops <= the ideal-conv census) while minimizing the
    interpret-mode grid overhead; the fused kernel targets the whole N
    (the kernels' VMEM cap sets the real step).
    """
    block_n = max(min(n // 2, 4096), 1)
    return TileChoice(block_n=block_n,
                      block_n_elem=max(min(n, 16384), 1),
                      block_n_fused=n,
                      fused=True)


def lookup(n: int, k_eff: int, c_out: int) -> Optional[TileChoice]:
    return _TABLE.get(shape_key(n, k_eff, c_out))


def put(n: int, k_eff: int, c_out: int, choice: TileChoice) -> None:
    _TABLE[shape_key(n, k_eff, c_out)] = choice


def clear() -> None:
    """Drop every in-process entry (tests)."""
    _TABLE.clear()


def get(n: int, k_eff: int, c_out: int) -> TileChoice:
    """The choice for a shape: tuned/loaded entry or the recorded default.

    First call on an untuned shape records the heuristic default, so every
    later call — and every jit trace — sees the identical choice.
    """
    key = shape_key(n, k_eff, c_out)
    if key not in _TABLE:
        _TABLE[key] = default_choice(n, k_eff, c_out)
    return _TABLE[key]


def resolve(n: int, k_eff: int, c_out: int,
            block_n: Optional[int] = None,
            block_n_elem: Optional[int] = None) -> Tuple[int, int]:
    """Concrete (block_n, block_n_elem) for a call: explicit values win,
    otherwise the table (tuned, loaded, or recorded default)."""
    if block_n is not None and block_n_elem is not None:
        return block_n, block_n_elem
    choice = get(n, k_eff, c_out)
    return (block_n if block_n is not None else choice.block_n,
            block_n_elem if block_n_elem is not None else choice.block_n_elem)


def resolve_fused(n: int, k_eff: int, c_out: int,
                  block_n: Optional[int] = None) -> int:
    """Concrete fused-kernel patch-row block (0 in the table = whole N)."""
    if block_n is not None:
        return block_n
    choice = get(n, k_eff, c_out)
    return choice.block_n_fused or n


def resolve_precision(n: int, k_eff: int, c_out: int,
                      precision: Optional[str] = None) -> str:
    """Concrete matmul precision for a call: explicit value wins, otherwise
    the table's tuned choice (``"f32"`` for untuned shapes)."""
    if precision is not None:
        if precision not in ("f32", "int8"):
            raise ValueError(f"unknown frontend precision {precision!r} "
                             "(expected 'f32' or 'int8')")
        return precision
    return get(n, k_eff, c_out).precision


def fleet_key(chips_in_batch: int, n: int, k_eff: int, c_out: int) -> TuneKey:
    """The table key of a fleet step: the chip axis is NOT part of it.

    A fleet step batches ``chips_in_batch`` chips over a leading vmap axis;
    inside the vmap every chip runs the SAME per-chip ``(N, K, C)`` kernel
    (the chip axis becomes an outer grid dimension, the tile geometry is
    per-chip), so the persisted single-chip ``TileChoice`` is exactly the
    right one — a ``(G, N, K, C)`` lookup that missed the table and re-tuned
    per chip mix would both waste a search and let the in-process table grow
    with the fleet.
    """
    del chips_in_batch
    return shape_key(n, k_eff, c_out)


def get_fleet(chips_in_batch: int, n: int, k_eff: int,
              c_out: int) -> TileChoice:
    """The choice a ``(chips_in_batch, N, K, C)`` fleet step runs with:
    the per-chip entry (tuned, loaded, or recorded default) — one table row
    serves every fleet size."""
    key = fleet_key(chips_in_batch, n, k_eff, c_out)
    if key not in _TABLE:
        _TABLE[key] = default_choice(*key)
    return _TABLE[key]


def resolve_fleet(chips_in_batch: int, n: int, k_eff: int, c_out: int,
                  block_n: Optional[int] = None,
                  block_n_elem: Optional[int] = None) -> Tuple[int, int]:
    """Concrete (block_n, block_n_elem) for one chip row of a fleet step."""
    del chips_in_batch
    return resolve(n, k_eff, c_out, block_n, block_n_elem)


def resolve_fleet_fused(chips_in_batch: int, n: int, k_eff: int, c_out: int,
                        block_n: Optional[int] = None) -> int:
    """Concrete fused-kernel block for one chip row of a fleet step."""
    del chips_in_batch
    return resolve_fused(n, k_eff, c_out, block_n)


def save_table(path: str) -> None:
    """Persist the in-process table as JSON ({"n,k,c": {...}}).

    A ``"_meta"`` entry (repro.obs.export.bench_meta) stamps the backend,
    device kind and jax version the timings were measured on;
    ``load_table`` refuses the table anywhere else.
    """
    from repro.obs.export import bench_meta
    table = {",".join(map(str, k)): v.to_json()
             for k, v in sorted(_TABLE.items())}
    table["_meta"] = bench_meta("autotune", entries=len(_TABLE))
    with open(path, "w") as f:
        json.dump(table, f, indent=2)


def load_table(path: str) -> int:
    """Merge a persisted table into the process; returns entries loaded.

    Tiles measured on one device say nothing about another (a table tuned
    on the CPU interpreter would size steps for a machine without VMEM), so
    a table whose ``"_meta"`` stamp names another backend or device kind —
    or that carries no stamp — raises ``ValueError``.
    """
    import jax
    with open(path) as f:
        raw = json.load(f)
    meta = raw.get("_meta", {})
    here = {"backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind}
    theirs = {k: meta.get(k, "unstamped") for k in here}
    if theirs != here:
        raise ValueError(f"tile table {path} was tuned on {theirs}, not on "
                         f"this {here}: re-run the search here")
    n = 0
    for k, v in raw.items():
        if k.startswith("_"):
            continue
        key = tuple(int(x) for x in k.split(","))
        _TABLE[key] = TileChoice.from_json(v)  # type: ignore[index]
        n += 1
    return n


# ---------------------------------------------------------------------------
# the search
# ---------------------------------------------------------------------------

def candidate_choices(n: int) -> Iterable[TileChoice]:
    """The deterministic two-kernel candidate grid for a shape.

    Every exact-path candidate is capped at ``n // 2`` — kernel A must keep
    >= 2 grid steps so its per-step matmul census stays within the
    1.2x-of-ideal budget that ``frontend_bench.py --quick`` gates; the
    tuner must be unable to trade that invariant away for wall clock.
    """
    cap = max(n // 2, 1)
    blocks = sorted({max(min(bn, cap), 1)
                     for bn in (256, 512, 1024, 2048, cap)})
    elems = sorted({max(min(be, n), 1) for be in (1024, 4096, 16384)})
    return tuple(TileChoice(bn, be) for bn in blocks for be in elems)


def fused_candidates(n: int) -> Iterable[int]:
    """The deterministic fused-kernel block candidates (incl. whole-N)."""
    return sorted({max(min(bn, n), 1) for bn in (512, 2048, max(n // 2, 1),
                                                 n)})


def _best_of(fn: Callable[[], None], repeats: int) -> float:
    fn()            # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = clock.now()
        fn()
        best = min(best, clock.now() - t0)
    return best


def autotune_frontend(images, w, v_th, key, *, kernel: int = 3,
                      stride: int = 2, chan=None,
                      pixel_params=None, mtj_params=None,
                      interpret: Optional[bool] = None, repeats: int = 3,
                      store: bool = True):
    """Measure the candidate grid for this call shape; return
    ``(TileChoice, report)`` and (by default) record the winner.

    ``report`` maps ``"block_n/block_n_elem"`` to the measured two-kernel
    and fused wall times (ms) — ``benchmarks/frontend_bench.py`` persists it
    so the chosen tiles are auditable. The fused flag is set if the fused
    streaming step at the winning tiles beats the two-kernel step. The fused
    candidates run at BOTH matmul precisions (``"fused"`` / ``"fused_q8"``
    report sections) and the winner's precision is recorded in the choice —
    the serving path then streams quantized wherever int8 measured faster.
    """
    import jax

    from repro.core import mtj as mtj_model
    from repro.core import pixel as pixel_model
    from repro.kernels import blocking, ops
    pixel_params = pixel_params or pixel_model.DEFAULT_PIXEL
    mtj_params = mtj_params or mtj_model.DEFAULT_MTJ
    b, h, wd, cin = images.shape
    ho, wo = blocking.conv_out_hw(h, stride), blocking.conv_out_hw(wd, stride)
    n = b * ho * wo
    k_eff = kernel * kernel * cin
    c_out = w.shape[-1]
    theta0 = v_th.reshape(1, 1).astype("float32")
    report: Dict[str, Dict[str, float]] = {"two_kernel": {}, "fused": {},
                                           "fused_q8": {}}
    base = dict(kernel=kernel, stride=stride, chan=chan,
                pixel_params=pixel_params, mtj_params=mtj_params,
                interpret=interpret)
    best_two: Tuple[float, Optional[TileChoice]] = (float("inf"), None)
    for cand in candidate_choices(n):
        kw = dict(base, block_n=cand.block_n, block_n_elem=cand.block_n_elem)

        def two_kernel():
            jax.block_until_ready(ops.p2m_frontend(images, w, v_th, key,
                                                   **kw)[0])

        ms = _best_of(two_kernel, repeats) * 1e3
        report["two_kernel"][f"{cand.block_n}/{cand.block_n_elem}"] = ms
        if ms < best_two[0]:
            best_two = (ms, cand)
    best_fused: Tuple[float, int, str] = (float("inf"), n, "f32")
    for bn in fused_candidates(n):
        for prec in ("f32", "int8"):
            kw = dict(base, block_n=bn, precision=prec)

            def fused():
                jax.block_until_ready(
                    ops.p2m_frontend_fused(images, w, v_th, theta0, key,
                                           **kw)[0])

            ms = _best_of(fused, repeats) * 1e3
            section = "fused" if prec == "f32" else "fused_q8"
            report[section][str(bn)] = ms
            if ms < best_fused[0]:
                best_fused = (ms, bn, prec)
    assert best_two[1] is not None
    choice = dataclasses.replace(best_two[1],
                                 block_n_fused=best_fused[1],
                                 fused=best_fused[0] < best_two[0],
                                 precision=best_fused[2])
    if store:
        put(n, k_eff, c_out, choice)
    return choice, report
