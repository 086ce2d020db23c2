"""``ExecutablePlan``: a PlanStore plan compiled for device execution.

``repro.api.compile(topo).executable(root, nbytes)`` is the one entry point:
it selects the best device-executable candidate from the BBS plan (or lowers
a named baseline through the same path), compiles the static
``DeviceSchedule`` tables, and hands back an object that runs, verifies and
times the broadcast on a jax device mesh:

    model = api.compile(T.ring(8, preset="tpu_ici"))
    ex = model.executable(root=0, nbytes=1 << 16)
    out = ex.run(x, mesh)          # jitted ppermute program, x read on the root's chip
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
    """Schedule tables + jitted runner + calibration hooks for one
    (plan, root, nbytes). Build through ``repro.api`` ``executable()`` or
    :func:`build_executable`.

    ``counters`` counts what ``run`` did: ``runs``, ``payload_moves`` (runs
    whose payload was not on the root's chip, so it was moved there first)
    and ``payload_moved_bytes``."""

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
    counters: dict = dataclasses.field(
        default_factory=lambda: {"runs": 0, "payload_moves": 0,
                                 "payload_moved_bytes": 0},
        compare=False, repr=False)

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
        """The jitted broadcast program: ``bbs_broadcast_split`` of the
        payload split over the mesh axis (``_split_input``)."""
        import jax
        from repro.device.runner import bbs_broadcast_split
        fn = self.__dict__.get("_run_fn")
        if fn is None:
            def run(split, mesh):
                return bbs_broadcast_split(
                    split, mesh, self.device.axis, self.schedule,
                    self.num_groups, use_pallas=self.device.use_pallas)
            fn = self._run_fn = jax.jit(run, static_argnums=1)
        return fn

    def _split_sharding(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(mesh, P(self.device.axis))

    def _split_input(self, x, mesh):
        """The program's input for payload ``x`` and the payload bytes moved
        to build it: ``x`` on the root's chip as that chip's shard, and on
        every other chip a placeholder of ``x``'s shape, which the program
        never reads for its values. Assembling the shards moves no data.

        ``x`` is used where it lies if it is on the root's chip alone, or
        the root chip's own shard is taken if ``x`` is replicated there;
        otherwise it is moved to the root's chip (the moved bytes)."""
        import jax
        import jax.numpy as jnp
        from repro.device.runner import root_chip
        if not np.ndim(x):
            raise ValueError("the payload needs at least one dimension; "
                             "broadcast x.reshape(1)")
        devices = list(mesh.devices.flat)
        root = devices[root_chip(self.schedule, len(devices))]
        moved = 0
        if not (isinstance(x, jax.Array) and x.is_fully_replicated
                and root in x.sharding.device_set):
            x = jax.device_put(x, root)
            moved = x.nbytes
        elif len(x.sharding.device_set) > 1:
            x = next(s.data for s in x.addressable_shards
                     if s.device == root)
        key = (mesh, x.shape, x.dtype)
        if self.__dict__.get("_placeholder_key") != key:
            # the last payload's placeholders only: one payload-sized
            # array on every chip but the root's
            self._placeholders = {
                d: jnp.zeros(x.shape, x.dtype, device=d)
                for d in devices if d != root}
            self._placeholder_key = key
        shards = [x if d == root else self._placeholders[d]
                  for d in devices]
        split = jax.make_array_from_single_device_arrays(
            (len(devices) * x.shape[0],) + x.shape[1:],
            self._split_sharding(mesh), shards)
        return split, moved

    def run(self, x, mesh=None):
        """Execute the broadcast; returns every fabric node's copy stacked
        on a leading axis (shape ``(num_nodes,) + x.shape``), whatever the
        number of devices the nodes are folded onto. ``x`` is read on the
        root's chip only (``_split_input``) and is not donated."""
        mesh = mesh or self.mesh()
        split, moved = self._split_input(x, mesh)
        self.counters["runs"] += 1
        self.counters["payload_moves"] += bool(moved)
        self.counters["payload_moved_bytes"] += moved
        return self._runner()(split, mesh)

    def lower(self, x, mesh=None):
        """The program ``run(x, mesh)`` dispatches, lowered for a payload
        of ``x``'s shape and dtype (``x`` an array or a
        ``jax.ShapeDtypeStruct``; where it lies does not change the
        program): ``.compile().as_text()`` is the optimized HLO, whose
        ``op_name`` metadata carries the ``bcast.*`` phase scopes. The
        program's input is the payload split over the mesh axis, one shard
        of ``x.shape`` a chip."""
        import jax
        mesh = mesh or self.mesh()
        split = jax.ShapeDtypeStruct(
            (mesh.devices.size * x.shape[0],) + tuple(x.shape[1:]), x.dtype,
            sharding=self._split_sharding(mesh))
        return self._runner().lower(split, mesh)

    def verify(self, x, mesh=None) -> DeviceDelivery:
        """Run and compare every node's buffer to the payload on raw bytes
        (``verify_delivery`` semantics: no faults => every node of the
        fabric must hold the complete message bit-identically)."""
        ref = np.asarray(x)
        out = np.asarray(self.run(x, mesh))
        required = tuple(range(self.topo.num_nodes))
        missing = tuple(v for v in required
                        if out[v].tobytes() != ref.tobytes())
        return DeviceDelivery(ok=not missing, required=required,
                              missing=missing)

    def measure(self, x=None, mesh=None, reps: int = 5) -> float:
        """Measured wall-clock seconds per broadcast (min over ``reps``
        timed runs after one warm-up compile), the calibration-side number
        compared against ``predicted_time``. The payload is placed on the
        root's chip before the timed runs."""
        import jax
        import jax.numpy as jnp
        mesh = mesh or self.mesh()
        if x is None:
            n = max(1, int(self.nbytes) // 4)
            x = jnp.arange(n, dtype=jnp.float32)
        fn = self._runner()
        split, _ = self._split_input(x, mesh)
        jax.block_until_ready(fn(split, mesh))      # compile + warm up
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(split, mesh))
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
