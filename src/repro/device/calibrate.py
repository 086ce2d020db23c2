"""Hockney calibration: fit per-link-class alpha/beta from measured rounds.

The simulator charges every send ``alpha + nbytes / beta`` per link
(``repro.core.topology.LINK_PRESETS`` hardcodes the constants per fabric
preset). ``calibrate`` closes the loop backwards: it times the *actual
round primitive the executor runs* — one ppermute matching plus the packed
scatter+gather step — on the live mesh across a ladder of payload sizes and
least-squares fits ``t(s) = alpha + s / beta``. The result is a
:class:`CalibratedCost` artifact that

  * the simulator consumes via :func:`apply_calibration` (a copy of the
    fabric with the fitted constants — new fingerprint, so PlanStore
    artifacts built against hardcoded constants are never silently reused);
  * ``benchmarks/roofline.py`` reads as JSON instead of its hardcoded
    ``LINK_BW`` fallback;
  * :func:`prediction_report` checks against reality: predicted vs measured
    per-cycle time for an :class:`ExecutablePlan`, the number the
    ``device_collective`` bench cell gates (<= 15% on the emulated mesh).

Emulated-mesh caveat: host "links" are memcpys through shared memory, so
the fitted alpha is dispatch overhead and beta is memory bandwidth — the
fit is a *self-consistency* check of the cost model, not silicon truth.
The same pass on a real TPU/GPU mesh yields fabric constants.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.topology import Topology

_MAGIC = "bbs-calibration"
_VERSION = 1


@dataclasses.dataclass
class CalibratedCost:
    """Fitted Hockney constants per link class.

    ``classes`` maps a link-class name (the fabric preset the plan charges,
    e.g. ``"tpu_ici"``, or ``"host"`` for the emulated mesh) to
    ``(alpha_seconds, beta_bytes_per_second)``. ``meta`` records the
    measurement environment (backend, device count, sample ladder, fit
    residual) so a consumer can judge the fit."""

    classes: Dict[str, Tuple[float, float]]
    meta: dict = dataclasses.field(default_factory=dict)

    def alpha(self, cls: str) -> float:
        return self.classes[cls][0]

    def beta(self, cls: str) -> float:
        return self.classes[cls][1]

    def round_time(self, cls: str, nbytes: float) -> float:
        a, b = self.classes[cls]
        return a + nbytes / b

    # -- JSON artifact (roofline and external consumers read this) ----------

    def to_dict(self) -> dict:
        return {"magic": _MAGIC, "version": _VERSION,
                "classes": {k: {"alpha": a, "beta": b}
                            for k, (a, b) in self.classes.items()},
                "meta": self.meta}

    @classmethod
    def from_dict(cls, d: dict) -> "CalibratedCost":
        if d.get("magic") != _MAGIC:
            raise ValueError(f"not a {_MAGIC} artifact: {d.get('magic')!r}")
        return cls(classes={k: (float(v["alpha"]), float(v["beta"]))
                            for k, v in d["classes"].items()},
                   meta=d.get("meta", {}))

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "CalibratedCost":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _fit_hockney(sizes: Sequence[float], times: Sequence[float],
                 ) -> Tuple[float, float, float]:
    """Least-squares t = alpha + s/beta; returns (alpha, beta, resid).
    alpha is clamped non-negative and beta positive (a noisy host timing
    ladder can produce a slightly negative intercept or slope)."""
    s = np.asarray(sizes, dtype=float)
    t = np.asarray(times, dtype=float)
    A = np.stack([np.ones_like(s), s], axis=1)
    (a, inv_b), res, _, _ = np.linalg.lstsq(A, t, rcond=None)
    a = max(float(a), 0.0)
    inv_b = max(float(inv_b), 1e-18)
    resid = float(np.sqrt(res[0] / len(t))) if len(res) else 0.0
    return a, 1.0 / inv_b, resid


def measure_round(mesh, axis: str, nbytes: int, *, iters: int = 32,
                  reps: int = 5, use_pallas: bool = False) -> float:
    """Measured seconds for one executor round at ``nbytes`` per link:
    a full ppermute ring matching (every device sends — the all-links-busy
    case the Hockney per-link charge models) followed by the packed
    scatter+gather step, min-of-``reps`` over an ``iters``-round scan."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.device.pallas_step import round_step

    n = mesh.shape[axis]
    pairs = [(i, (i + 1) % n) for i in range(n)]
    elems = max(1, int(nbytes) // 4)
    x = jnp.zeros((2, elems), jnp.float32)

    def body(buf):
        def step(buf, _):
            val = buf[0]
            rec = jax.lax.ppermute(val, axis, pairs)
            buf, _val = round_step(buf, rec, 1, True, 0, True,
                                   use_pallas=use_pallas)
            return buf, ()
        buf, _ = jax.lax.scan(step, buf, None, length=iters)
        return buf[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P(axis), check_vma=False))
    jax.block_until_ready(fn(x))                 # compile + warm up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    return best / iters


def calibrate(topo: Optional[Topology], mesh, axis: str = "dev", *,
              sizes: Optional[Sequence[int]] = None, iters: int = 32,
              reps: int = 5, cls: Optional[str] = None,
              emulated: Optional[bool] = None) -> CalibratedCost:
    """Fit Hockney alpha/beta for the mesh's link class.

    The class name defaults to the fabric's link preset (what the plan's
    simulator charge is keyed by) so :func:`apply_calibration` and the
    roofline lookup find it; homogeneous fabrics have one class, which is
    all a flat device mesh can measure."""
    import jax
    if sizes is None:
        sizes = (1 << 10, 8 << 10, 64 << 10, 256 << 10, 1 << 20)
    times = [measure_round(mesh, axis, s, iters=iters, reps=reps)
             for s in sizes]
    a, b, resid = _fit_hockney(sizes, times)
    if cls is None:
        cls = getattr(topo, "_preset", None) or "host"
    backend = jax.devices()[0].platform
    if emulated is None:
        emulated = backend == "cpu"
    meta = {"backend": backend, "num_devices": int(np.prod(mesh.devices.shape)),
            "emulated": bool(emulated), "axis": axis,
            "sizes": [int(s) for s in sizes], "round_seconds": times,
            "fit_residual_seconds": resid, "iters": iters, "reps": reps}
    return CalibratedCost(classes={cls: (a, b)}, meta=meta)


def apply_calibration(topo: Topology, cost: CalibratedCost,
                      cls: Optional[str] = None) -> Topology:
    """A copy of the fabric whose link constants are the fitted ones.

    The copy gets a new name and (through the changed constants) a new
    ``topology_fingerprint``, so plans built against hardcoded presets are
    rebuilt rather than silently reused. Flat fabrics only — hierarchical
    link classes (nic/trunk) need per-class measurement a flat device mesh
    cannot provide."""
    import copy
    if getattr(topo, "hierarchical", False):
        raise ValueError("apply_calibration supports flat fabrics only")
    if cls is None:
        cls = getattr(topo, "_preset", None)
        if cls not in cost.classes:
            cls = next(iter(cost.classes))
    a, b = cost.classes[cls]
    t = copy.copy(topo)
    t.name = f"{topo.name}@{cls}"
    t._lat = a
    t._bw = b
    return t


@dataclasses.dataclass
class PredictionRow:
    """One (topology, message size) line of the calibration report."""

    topo: str
    candidate: str
    nbytes: float
    num_cycles: int
    predicted_cycle_s: float
    measured_cycle_s: float

    @property
    def rel_err(self) -> float:
        m = self.measured_cycle_s
        return abs(self.predicted_cycle_s - m) / m if m > 0 else 0.0


def predict_cycle_time(ex, cost: CalibratedCost,
                       cls: Optional[str] = None) -> float:
    """Fitted-model prediction of one pipeline cycle: the d sub-round
    matchings serialize, each shipping one packet row per device."""
    if cls is None:
        cls = getattr(ex.topo, "_preset", None)
        if cls not in cost.classes:
            cls = next(iter(cost.classes))
    sched = ex.schedule
    elems = max(1, int(ex.nbytes) // 4)
    rows = sched.K * ex.num_groups
    row_bytes = (-(-elems // rows)) * 4
    return sched.d * cost.round_time(cls, row_bytes)


def prediction_report(executables: Sequence, cost: CalibratedCost,
                      mesh=None, reps: int = 5) -> List[PredictionRow]:
    """Predicted-vs-measured per-cycle step time for each executable —
    the report the acceptance bound (<= 15% emulated) is checked on."""
    rows = []
    for ex in executables:
        m = mesh or ex.mesh()
        cycles = ex.schedule.num_cycles(ex.num_groups)
        measured = ex.measure(mesh=m, reps=reps) / cycles
        rows.append(PredictionRow(
            topo=ex.topo.name, candidate=ex.candidate, nbytes=ex.nbytes,
            num_cycles=cycles, predicted_cycle_s=predict_cycle_time(ex, cost),
            measured_cycle_s=measured))
    return rows
