"""Kernelized round engine: jit exactness, dispatch policy, lane batching.

The contract under test (docs/engines.md §kernelized round step): the
jitted core is bit-identical to the numpy engine — not approximately
equal — on every lowered list it accepts, and every capability it lacks
(faults, foldable lists, missing jax) delegates to the numpy engine
rather than approximating. The jit policy (``REPRO_KERNEL_JIT`` /
host CPU device count) is a pure performance choice, never a semantic
one, and the core runs on the host CPU whatever accelerator is present.
"""

import numpy as np
import pytest

from repro.core import kernelsim as KS
from repro.core import topology as T
from repro.core.baselines import lower_baseline, simulate_baseline
from repro.core.fastsim import CompiledSim, TaskListRun
from repro.core.intersection import ALL_PORT, FULL_DUPLEX, ConflictModel
from repro.core.simconfig import SimConfig

needs_jax = pytest.mark.skipif(not KS.KERNEL_AVAILABLE,
                               reason="jax unavailable")

TOPOS = [
    ("mesh2d-4x6", lambda: T.mesh2d(4, 6), FULL_DUPLEX),
    ("mesh2d-16x16", lambda: T.mesh2d(16, 16), FULL_DUPLEX),
    ("dragonfly", lambda: T.dragonfly(4, 4, 2), ALL_PORT),
    ("fat_tree", lambda: T.fat_tree(4), FULL_DUPLEX),
]
NAMES = ["binomial", "flat", "pipeline", "srda", "glf", "bine", "mpi_bcast"]


def _same(a, b):
    return (a.finish_time == b.finish_time and a.deliveries == b.deliveries
            and a.node_finish == b.node_finish
            and a.group_finish == b.group_finish
            and a.started == b.started and a.completed == b.completed)


@needs_jax
@pytest.mark.parametrize("tname,mk,mode", TOPOS, ids=[t[0] for t in TOPOS])
@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("size", [4e4, 64e6])
def test_forced_jit_bit_identical(tname, mk, mode, name, size):
    topo = mk()
    cm = ConflictModel(topo, mode)
    ctl = lower_baseline(topo, cm, name, 0, size)
    ref = CompiledSim(topo, cm, 0).run_lowered(ctl)
    got = KS.KernelSim(topo, cm, 0).run_lowered(ctl, jit=True)
    assert _same(got, ref)


@needs_jax
@pytest.mark.parametrize("jit", [True, False])
def test_lane_batch_matches_per_size_runs(jit):
    topo = T.mesh2d(16, 16)
    cm = ConflictModel(topo, FULL_DUPLEX)
    nsim = CompiledSim(topo, cm, 0)
    ks = KS.KernelSim(topo, cm, 0)
    sizes = np.geomspace(1e5, 1e9, 12).tolist()
    ctl, durs, nbytes = KS.lower_baseline_lanes(topo, cm, "binomial", 0,
                                                sizes)
    refs = [nsim.run_lowered(lower_baseline(topo, cm, "binomial", 0, s))
            for s in sizes]
    got = ks.run_lowered_batch(ctl, durs, nbytes, jit=jit)
    assert all(_same(g, r) for g, r in zip(got, refs))


@needs_jax
def test_lane_batch_foldable_goes_through_folded_core():
    # srda on a non-power-of-two node count lowers to the ring allgather,
    # which folds; the batch must route lanes through the (bit-identical)
    # folded numpy core, never the flat kernel
    topo = T.mesh2d(4, 6)
    cm = ConflictModel(topo, FULL_DUPLEX)
    nsim = CompiledSim(topo, cm, 0)
    ks = KS.KernelSim(topo, cm, 0)
    sizes = [4e6, 16e6, 64e6]
    ctl, durs, nbytes = KS.lower_baseline_lanes(topo, cm, "srda", 0, sizes)
    assert ctl.seg is not None and ctl.seg.foldable
    refs = [nsim.run_lowered(lower_baseline(topo, cm, "srda", 0, s))
            for s in sizes]
    got = ks.run_lowered_batch(ctl, durs, nbytes, jit=True)
    assert all(_same(g, r) for g, r in zip(got, refs))


def test_lane_batching_rejects_chain_family():
    # the chain family re-segments per message size: no shared structure
    topo = T.mesh2d(4, 6)
    cm = ConflictModel(topo, FULL_DUPLEX)
    with pytest.raises(ValueError, match="lowered structure"):
        KS.lower_baseline_lanes(topo, cm, "pipeline", 0, [4e6, 64e6])


@needs_jax
def test_foldable_list_never_reaches_the_jit_core(monkeypatch):
    topo = T.mesh2d(4, 6)
    cm = ConflictModel(topo, FULL_DUPLEX)
    ks = KS.KernelSim(topo, cm, 0)
    ctl = lower_baseline(topo, cm, "srda", 0, 64e6)
    assert ctl.seg is not None and ctl.seg.foldable

    def boom(*a, **k):
        raise AssertionError("foldable list hit the jit core")

    monkeypatch.setattr(KS, "_CORE", boom)
    ref = CompiledSim(topo, cm, 0).run_lowered(ctl)
    assert _same(ks.run_lowered(ctl, jit=True), ref)


def test_without_jax_everything_delegates(monkeypatch):
    monkeypatch.setattr(KS, "KERNEL_AVAILABLE", False)
    topo = T.mesh2d(4, 6)
    cm = ConflictModel(topo, FULL_DUPLEX)
    ks = KS.KernelSim(topo, cm, 0)
    ctl = lower_baseline(topo, cm, "binomial", 0, 64e6)
    ref = CompiledSim(topo, cm, 0).run_lowered(ctl)
    assert _same(ks.run_lowered(ctl, jit=True), ref)
    durs = np.asarray([ctl.durs], dtype=np.float64)
    got = ks.run_lowered_batch(ctl, durs)
    assert len(got) == 1 and _same(got[0], ref)


def test_faults_delegate_to_numpy_fault_loop():
    from repro.core import faults as F
    from repro.core.baselines import BASELINES

    topo = T.mesh2d(4, 6)
    cm = ConflictModel(topo, FULL_DUPLEX)
    tasks = BASELINES["binomial"](topo, 0, 1e6)
    tb = max(t.blk[1] for t in tasks)
    link = topo.links((0, 1))[0]
    sched = F.FaultSchedule.kill_link(link, time=1e-6)
    ref = CompiledSim(topo, cm, 0).run(tasks, total_blocks=tb, faults=sched)
    got = KS.KernelSim(topo, cm, 0).run(tasks, total_blocks=tb,
                                        faults=sched)
    assert got.finish_time == ref.finish_time
    assert got.faults.events_applied == ref.faults.events_applied


@needs_jax
def test_run_task_list_interception():
    topo = T.mesh2d(16, 16)
    cm = ConflictModel(topo, FULL_DUPLEX)
    ks = KS.KernelSim(topo, cm, 0)
    ctl = lower_baseline(topo, cm, "binomial", 0, 64e6)
    ref = CompiledSim(topo, cm, 0).run_lowered(ctl)
    tlr = ks.run_task_list(lowered=ctl, jit=True)
    assert isinstance(tlr, TaskListRun)
    assert tlr.sim_segments == 0 and _same(tlr.res, ref)


def test_jit_policy_env(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_JIT", "force")
    assert KS._jit_default() is True
    monkeypatch.setenv("REPRO_KERNEL_JIT", "0")
    assert KS._jit_default() is False
    monkeypatch.delenv("REPRO_KERNEL_JIT")
    if not KS.KERNEL_AVAILABLE:
        assert KS._jit_default() is False
        return
    import jax
    assert KS._jit_default() is (len(jax.devices("cpu")) > 1)
    # a 4-chip TPU host with one host CPU device: the chips do not count,
    # and the core stays on the host CPU
    cpu = jax.devices("cpu")[:1]
    chips = ["tpu"] * 4
    monkeypatch.setattr(jax, "devices",
                        lambda backend=None: cpu if backend == "cpu"
                        else chips)
    monkeypatch.setattr(jax, "device_count",
                        lambda backend=None: len(jax.devices(backend)))
    assert KS._jit_default() is False
    assert KS._core_device() is cpu[0]


@needs_jax
def test_jit_core_runs_on_the_host_cpu(monkeypatch):
    topo = T.mesh2d(4, 6)
    cm = ConflictModel(topo, FULL_DUPLEX)
    ctl = lower_baseline(topo, cm, "binomial", 0, 64e6)
    real = KS._CORE
    seen = []

    def spy(*args):
        seen.extend(a.devices() for a in args)
        return real(*args)

    monkeypatch.setattr(KS, "_CORE", spy)
    ref = CompiledSim(topo, cm, 0).run_lowered(ctl)
    assert _same(KS.KernelSim(topo, cm, 0).run_lowered(ctl, jit=True), ref)
    assert seen and all(d == {KS._core_device()} for d in seen)


@pytest.mark.parametrize("name", ["binomial", "srda", "pipeline", "glf"])
def test_api_kernel_engine_matches_fast(name):
    topo = T.mesh2d(16, 16)
    cm = ConflictModel(topo, FULL_DUPLEX)
    rk = simulate_baseline(topo, cm, name, 0, 64e6,
                           config=SimConfig(engine="kernel"))
    rf = simulate_baseline(topo, cm, name, 0, 64e6,
                           config=SimConfig(engine="fast"))
    assert _same(rk, rf)
