"""One-call facade over the broadcast-simulation stack.

Every workflow in this repo starts the same way: build a ``Topology``,
wrap it in a ``ConflictModel``, share the compiled routing layer, maybe
stand up a ``PlanServer`` for orbit-canonical plan reuse. ``compile``
does that once and hands back a ``CompiledModel`` whose methods mirror
the module-level entry points (``repro.core.bbs.broadcast_time``,
``repro.core.simulator.simulate_pipeline``,
``repro.core.baselines.simulate_baseline``,
``repro.workload.run_workload``) with the shared state already threaded
through::

    from repro import api
    from repro.core import topology as T

    model = api.compile(T.mesh2d(16, 16))
    t, info = model.broadcast_time(root=0, nbytes=16e6)
    res = model.simulate_baseline("binomial", root=0, nbytes=16e6)
    report = model.workload(jobs)          # concurrent multi-root load
    ex = model.executable(root=0, nbytes=1 << 16)   # device execution

Simulation options ride a single ``config=SimConfig(...)`` object
(``repro.core.simconfig``) rather than per-function keyword sprawl; the
old per-function keywords still work everywhere through a deprecation
shim with bit-identical results. ``SimConfig(engine="kernel")`` routes
baseline task lists through the jax-jitted round core
(``repro.core.kernelsim``) with the numpy engine as bit-identical
fallback everywhere the kernel does not apply.

The facade adds no policy of its own — every method delegates to the
underlying module function, so results are bit-identical to calling
those functions directly with the same shared ``ConflictModel``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

from repro.core.intersection import FULL_DUPLEX, ConflictModel
from repro.core.routing import topology_fingerprint
from repro.core.simconfig import SimConfig
from repro.core.topology import Topology


def compile(topo: Topology, mode: str = FULL_DUPLEX, *,
            server: bool = False, store=None,
            plan_capacity: int = 256) -> "CompiledModel":
    """Compile ``topo`` once for the whole simulation stack.

    Builds the ``ConflictModel`` (and through it the shared
    ``CompiledTopology`` resource layer every engine call reuses) and,
    when ``server=True`` or a ``store`` is given, a ``PlanServer`` whose
    orbit-canonical caches back ``plan``/``broadcast_time``/``workload``
    lookups. ``store`` (a ``repro.core.planstore.PlanStore``) persists
    canonical builds on disk across processes."""
    cm = ConflictModel(topo, mode)
    model = CompiledModel(topo=topo, cm=cm, mode=mode)
    if server or store is not None:
        model.ensure_server(store=store, plan_capacity=plan_capacity)
    return model


@dataclasses.dataclass
class CompiledModel:
    """A topology compiled for simulation: shared ``ConflictModel`` +
    routing layer, optional warm ``PlanServer`` (see ``compile``)."""

    topo: Topology
    cm: ConflictModel
    mode: str = FULL_DUPLEX
    server: Optional[object] = None          # repro.launch.planserver

    @property
    def compiled(self):
        """The shared ``repro.core.routing.CompiledTopology``."""
        return self.cm.compiled()

    @property
    def fingerprint(self) -> str:
        return topology_fingerprint(self.topo)

    def ensure_server(self, store=None, plan_capacity: int = 256):
        """Attach (or return) the model's ``PlanServer`` — plan queries
        then share one orbit-canonicalizing cache across roots."""
        if self.server is None:
            from repro.launch.planserver import PlanServer
            self.server = PlanServer(store=store,
                                     plan_capacity=plan_capacity,
                                     mode=self.mode)
            self.server.register(self.topo)
        return self.server

    # -- plans ---------------------------------------------------------------

    def plan(self, root: int = 0):
        """The BBS plan for ``root`` — served (and cached, with orbit
        relabeling) by the attached ``PlanServer`` when there is one,
        else built directly on the shared ``ConflictModel``."""
        if self.server is not None:
            return self.server.plan(self.topo, root)
        from repro.core.bbs import build_plan
        return build_plan(self.topo, root=root, mode=self.mode, cm=self.cm)

    def broadcast_time(self, root: int, nbytes: float, *,
                       config: Optional[SimConfig] = None,
                       ) -> Tuple[float, dict]:
        """Predicted broadcast time + selection info for ``nbytes`` from
        ``root`` (``repro.core.bbs.broadcast_time`` on ``plan(root)``)."""
        from repro.core.bbs import broadcast_time
        return broadcast_time(self.plan(root), nbytes, config=config)

    # -- single-run simulation ------------------------------------------------

    def simulate_pipeline(self, pipe, message_bytes: float,
                          num_groups: int, root: int, *,
                          config: Optional[SimConfig] = None):
        from repro.core.simulator import simulate_pipeline
        return simulate_pipeline(self.topo, self.cm, pipe, message_bytes,
                                 num_groups, root, config=config)

    def simulate_baseline(self, name: str, root: int, nbytes: float, *,
                          store=None, config: Optional[SimConfig] = None):
        from repro.core.baselines import simulate_baseline
        return simulate_baseline(self.topo, self.cm, name, root, nbytes,
                                 store=store, config=config)

    # -- device execution -----------------------------------------------------

    def executable(self, root: int, nbytes: float, *, algo: str = "bbs",
                   config: Optional[SimConfig] = None):
        """Compile ``(root, nbytes)`` for device execution — an
        ``repro.device.ExecutablePlan`` with static ppermute tables, a
        jitted runner, and calibration hooks.

        ``algo="bbs"`` executes the best device-executable candidate of
        ``plan(root)`` (PlanServer-relabeled plans, pinned route overrides
        included, flow through unchanged); a baseline name (``"binomial"``,
        ``"bine_tree"``, ...) lowers that baseline's whole-message tree
        through the same ``build_pipeline`` -> ``DeviceSchedule`` path."""
        from repro.device import build_executable
        plan = self.plan(root) if algo == "bbs" else None
        return build_executable(self.topo, self.cm, root, nbytes,
                                algo=algo, plan=plan, config=config)

    # -- concurrent workloads -------------------------------------------------

    def workload(self, jobs: Sequence, faults=None, *,
                 config: Optional[SimConfig] = None):
        """Run a multi-root broadcast workload (``repro.workload``) on
        this model's shared resource layer; returns a
        ``WorkloadReport``."""
        from repro.workload import run_workload
        return run_workload(self, jobs, faults=faults, config=config)
