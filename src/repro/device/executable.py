"""``ExecutablePlan``: a PlanStore plan compiled for device execution.

``repro.api.compile(topo).executable(root, nbytes)`` is the one entry point:
it selects the best device-executable candidate from the BBS plan (or lowers
a named baseline through the same path), compiles the static
``DeviceSchedule`` tables, and hands back an object that runs, verifies and
times the broadcast on a jax device mesh:

    model = api.compile(T.ring(8, preset="tpu_ici"))
    ex = model.executable(root=0, nbytes=1 << 16)
    out = ex.run(x, mesh)          # donated-buffer jitted ppermute program
    chk = ex.verify(x, mesh)       # bit-exact delivery on every device
    cal = ex.calibrate(mesh)       # fitted Hockney alpha/beta per link class

Baselines lower through the identical machinery: the whole-message task
list is folded back into its arborescence, colored into conflict-free
rounds (``repro.core.schedule.build_pipeline``) and compiled into the same
tables — multi-hop virtual edges (Bine's negabinary strides on a ring)
become relay chains inside the cycle (``repro.device.schedule``).

``verify`` enforces the no-fault contract of
``repro.core.faults.verify_delivery``: every node is reachable from the
root, so every node's received buffer must be bit-identical to the payload
(compared on raw bytes — bfloat16/NaN safe).
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Tuple

import numpy as np

from repro.core.intersection import ConflictModel
from repro.core.simconfig import DeviceConfig, SimConfig
from repro.core.topology import Topology
from repro.device.schedule import (DeviceSchedule, NotDeviceExecutable,
                                   make_device_schedule)


@dataclasses.dataclass
class DeviceDelivery:
    """Bit-exact delivery check (the device rendering of
    ``repro.core.faults.DeliveryCheck``): with no faults every node is
    required; ``missing`` lists nodes whose buffer differs from the
    payload."""

    ok: bool
    required: Tuple[int, ...]
    missing: Tuple[int, ...]


@dataclasses.dataclass
class ExecutablePlan:
    """Schedule tables + donated-buffer runner + calibration hooks for one
    (plan, root, nbytes). Build through ``repro.api`` ``executable()`` or
    :func:`build_executable`."""

    topo: Topology
    cm: ConflictModel
    root: int
    nbytes: float
    algo: str                     # "bbs" or a baseline name
    candidate: str                # winning candidate (bbs) / algo name
    schedule: DeviceSchedule
    num_groups: int
    predicted_time: float         # simulator prediction for this selection
    info: dict
    device: DeviceConfig
    pipeline: object = None       # the compiled Pipeline (calibration reads it)

    # -- runners -------------------------------------------------------------

    def mesh(self, devices=None):
        """The execution mesh: one flat axis over ``devices``, or by
        default over the first ``mesh_shape`` devices, else over as many
        of ``jax.devices()`` as evenly divide the fabric's node count (one
        node per device where there are enough). The fabric's nodes fold
        onto the mesh in contiguous blocks (``fold_schedule``)."""
        import jax
        from jax.sharding import Mesh
        from repro.device.runner import device_mesh
        n = self.topo.num_nodes
        if devices is not None:
            k = len(devices)
        elif self.device.mesh_shape:
            k = int(np.prod(self.device.mesh_shape))
        else:
            have = len(jax.devices())
            k = max(d for d in range(1, min(have, n) + 1) if n % d == 0)
        if n % k:
            raise ValueError(
                f"a mesh of {k} devices cannot hold the fabric's {n} nodes "
                f"in equal blocks")
        if devices is None:
            return device_mesh(k, axis=self.device.axis)
        return Mesh(np.array(devices), (self.device.axis,))

    def _runner(self):
        import jax
        from repro.device.runner import bbs_broadcast
        fn = self.__dict__.get("_run_fn")
        if fn is None:
            def run(x, mesh):
                return bbs_broadcast(
                    x, mesh, self.device.axis, self.schedule,
                    self.num_groups, use_pallas=self.device.use_pallas)
            # donate the payload buffer: the packet buffer is rewritten in
            # place across the scan, so the input allocation is reusable
            fn = self._run_fn = jax.jit(run, static_argnums=1,
                                        donate_argnums=0)
        return fn

    def run(self, x, mesh=None):
        """Execute the broadcast; returns every fabric node's copy stacked
        on a leading axis (shape ``(num_nodes,) + x.shape``), whatever the
        number of devices the nodes are folded onto."""
        mesh = mesh or self.mesh()
        return self._runner()(x, mesh)

    def lower(self, x, mesh=None):
        """The program ``run(x, mesh)`` dispatches, lowered for ``x`` (an
        array or a ``jax.ShapeDtypeStruct`` with its sharding):
        ``.compile().as_text()`` is the optimized HLO, whose ``op_name``
        metadata carries the ``bcast.*`` phase scopes."""
        return self._runner().lower(x, mesh or self.mesh())

    def verify(self, x, mesh=None) -> DeviceDelivery:
        """Run and compare every node's buffer to the payload on raw bytes
        (``verify_delivery`` semantics: no faults => every node of the
        fabric must hold the complete message bit-identically)."""
        import jax.numpy as jnp
        # non-destructive: the runner donates its payload, so run a copy
        # and keep the caller's array (and our reference bytes) intact
        ref = np.asarray(x).copy()
        out = np.asarray(self.run(jnp.asarray(ref.copy()), mesh))
        required = tuple(range(self.topo.num_nodes))
        missing = tuple(v for v in required
                        if out[v].tobytes() != ref.tobytes())
        return DeviceDelivery(ok=not missing, required=required,
                              missing=missing)

    def measure(self, x=None, mesh=None, reps: int = 5) -> float:
        """Measured wall-clock seconds per broadcast (min over ``reps``
        timed runs after one warm-up compile), the calibration-side number
        compared against ``predicted_time``."""
        import jax
        import jax.numpy as jnp
        mesh = mesh or self.mesh()
        if x is None:
            n = max(1, int(self.nbytes) // 4)
            x = jnp.arange(n, dtype=jnp.float32)
        ref = np.asarray(x)
        fn = self._runner()
        # the runner donates its payload, so every call needs a fresh
        # buffer; allocate them outside the timed region
        xs = [jnp.asarray(ref.copy()) for _ in range(reps + 1)]
        jax.block_until_ready(fn(xs[0], mesh))      # compile + warm up
        best = float("inf")
        for i in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(xs[i + 1], mesh))
            best = min(best, time.perf_counter() - t0)
        return best

    def calibrate(self, mesh=None, **kw):
        """Fit per-link-class Hockney alpha/beta from measured round times
        on this plan's mesh — see ``repro.device.calibrate``."""
        from repro.device.calibrate import calibrate
        return calibrate(self.topo, mesh or self.mesh(),
                         axis=self.device.axis, **kw)


def build_executable(topo: Topology, cm: ConflictModel, root: int,
                     nbytes: float, *, algo: str = "bbs",
                     plan=None, store=None,
                     config: Optional[SimConfig] = None) -> ExecutablePlan:
    """Compile ``(root, nbytes)`` into an :class:`ExecutablePlan`.

    ``algo="bbs"`` walks the plan's Eq.-4 candidate ranking and takes the
    best candidate whose pipeline compiles to ppermute matchings
    (``NotDeviceExecutable`` candidates are skipped); a baseline name takes
    that baseline's whole-message tree through ``build_pipeline``. ``plan``
    short-circuits the BBS plan build (the PlanServer hands relabeled plans
    through here — their pinned route overrides are honored by the schedule
    compiler)."""
    cfg = config or SimConfig()
    dev = cfg.device or DeviceConfig()
    n = topo.num_nodes
    compiled = cm.compiled()

    if algo == "bbs":
        if plan is None:
            from repro.core.bbs import build_plan
            plan = build_plan(topo, root=root, mode=cm.mode, cm=cm)
        errors: List[str] = []
        for cand, m in plan.select(nbytes, top=len(plan.candidates)):
            try:
                sched = make_device_schedule(cand.pipeline, n,
                                             compiled=compiled)
            except NotDeviceExecutable as e:
                errors.append(f"{cand.name}: {e}")
                continue
            t = cand.t_opt(nbytes, plan.L, plan.B)
            return ExecutablePlan(
                topo=topo, cm=cm, root=root, nbytes=float(nbytes),
                algo="bbs", candidate=cand.name, schedule=sched,
                num_groups=m, predicted_time=t,
                info={"m_opt": m, "candidates_skipped": errors},
                device=dev, pipeline=cand.pipeline)
        raise NotDeviceExecutable(
            f"no BBS candidate for root {root} compiles to a device "
            f"schedule: {errors}")

    # baseline path: rebuild the whole-message arborescence from the task
    # list and lower it through the standard pipeline coloring
    from repro.core import baselines as B
    from repro.core.arborescence import Arborescence
    from repro.core.schedule import build_pipeline
    tasks = B.BASELINES[algo](topo, root, nbytes)
    parent = {}
    for t in tasks:
        if t.blk != (0, 1):
            raise NotDeviceExecutable(
                f"baseline {algo!r} is not a whole-message tree (task blocks "
                f"{t.blk}); only tree baselines execute on device")
        if t.dst in parent:
            raise NotDeviceExecutable(
                f"baseline {algo!r} delivers node {t.dst} twice; not a tree")
        parent[t.dst] = t.src
    tree = Arborescence(root=root, parent=parent)
    pipe = build_pipeline(topo, [tree], cm)
    sched = make_device_schedule(pipe, n, compiled=compiled)
    res = B.simulate_baseline(topo, cm, algo, root, nbytes,
                              config=SimConfig(engine=cfg.engine))
    return ExecutablePlan(
        topo=topo, cm=cm, root=root, nbytes=float(nbytes), algo=algo,
        candidate=algo, schedule=sched, num_groups=1,
        predicted_time=res.finish_time, info={"baseline": algo},
        device=dev, pipeline=pipe)
