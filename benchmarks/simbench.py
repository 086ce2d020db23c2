"""Simulator-engine microbenchmarks: reference oracle vs round-batched engine.

Four measurements, CSV ``name,value,derived`` on stdout (matching
benchmarks/run.py conventions) plus a machine-readable ``BENCH_simbench.json``
so the perf trajectory is tracked across PRs (uploaded as a CI artifact by
``bench-smoke``):

  raw_run        tasks/sec of EventSimulator.run vs CompiledSim.run on the
                 *identical* expanded task list (generic task-list loop)
  baseline       the routed-baseline raw loop: simulate_baseline through the
                 memoized ``CompiledTaskList`` lowering (segment folding for
                 the chain family) vs the seed-era generic ``CompiledSim.run``
                 path (per-call interning + bitmap coverage, frozen below as
                 ``_seed_generic_run`` and asserted bit-identical before any
                 speedup is reported). One record per algorithm plus the
                 geometric-mean headline cell; CPU-time, interleaved reps
  raw_pipeline   the raw (non-analytic) pipeline event loop: reference =
                 expand m groups + simulate; fast = the template core
                 simulating every group (steady/cycle analytics disabled).
                 Results are asserted bit-identical before the speedup is
                 reported — the acceptance cell (mesh2d n=256, 16 groups)
  pipeline       end-to-end pipelined broadcast with analytics on: the fast
                 engine simulates a prefix and extrapolates (chain pipelines
                 are exactly periodic, so the extrapolation is exact here;
                 asserted rel 1e-9)
  cycle          the verified occupancy-cycle path on a jittery two_tree
                 schedule (ring16 all-port): detector must fire and match
                 the full non-analytic run to 1e-9
  build_plan     wall time of bbs.build_plan per topology with the fast
                 engine (the end-to-end "plan once offline" cost; the m=1
                 fill time now comes from an exact isolated group-0 replay).
                 Gated as a *ceiling* (build_plan_seconds) so plan builds
                 cannot silently balloon
  plan_cache     symmetry-orbit plan sharing: assembling the all-roots
                 packed artifact through orbit canonicalization + witness
                 relabeling (k builds for k orbits) vs the per-root build
                 cost sampled and extrapolated to all n roots. Relabeled
                 plans are spot-asserted to answer identically to fresh
                 builds before the speedup is reported. Two fabrics per
                 profile: mesh2d (D4 symmetry — n/8-ish orbits bound the
                 win) and torus2d (vertex-transitive — one orbit, the
                 paper-table regime where sharing collapses the whole
                 build). Also serves a root-symmetric request stream
                 through ``repro.launch.planserver.PlanServer`` and
                 records the warm-cache hit rate (gated >= 0.9)
  kernel_sweep   the kernel engine's adaptive dispatch
                 (``repro.core.kernelsim.KernelSim``) running a grid-sweep
                 row — every task-list family x two message sizes on one
                 mesh — vs the same lowered lists forced down the plain
                 generic round loop (``seg = None`` copies: the path every
                 list took before folding). Bit-identity is asserted per
                 (family, size) before timing; the gated headline is the
                 aggregate tasks/s ratio, dominated by the chain-family
                 fold — per-family components are printed so the cell
                 cannot hide a regression in the flat families. On this
                 single-core CI host the dispatch routes to the numpy
                 paths (the jitted core pays off on multi-device hosts and
                 is exercised for exactness in tests/test_kernel.py)
  workload       concurrent multi-root broadcast workloads
                 (``repro.workload``): fixed-seed offered-load sweep over
                 one corner orbit of the mesh; the sustained jobs/s at the
                 heaviest (saturated) point is the gated capacity cell —
                 simulated time, so it is deterministic per profile
  device_collective  the sim-to-silicon loop (``repro.device``) on an
                 emulated 8-device host mesh (subprocess with
                 ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
                 and ``JAX_PLATFORMS=cpu``, so it never reaches for a
                 chip the parent holds):
                 executes the compiled BBS plan end to end, gates the
                 measured cycle throughput (floor) and the Hockney-
                 calibration prediction error (ceiling, the paper-facing
                 <=15% bound), and refreshes the CalibratedCost JSON
                 artifact ``benchmarks/artifacts/calibration.json`` that
                 ``benchmarks/roofline.py`` consumes

Usage:
  PYTHONPATH=src python -m benchmarks.simbench            # full (n=256)
  PYTHONPATH=src python -m benchmarks.simbench --smoke    # small + quick
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_RECORDS = []


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _best_of_cpu_interleaved(fns, repeats: int, target_s: float = 0.6):
    """Best-of CPU time per function, interleaving the contenders on every
    repeat (A B A B ... rather than A A B B) so drift on a noisy box hits
    both sides alike. Each timed sample loops the function enough times to
    outlast the CPU-clock quantum; returns per-call seconds."""
    iters = []
    for fn in fns:
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        iters.append(max(1, int(target_s / max(dt, 1e-9))))
    best = [float("inf")] * len(fns)
    for _ in range(repeats):
        for j, fn in enumerate(fns):
            t0 = time.process_time()
            for _ in range(iters[j]):
                fn()
            best[j] = min(best[j], (time.process_time() - t0) / iters[j])
    return best


_SEED_BATCH_MIN_READY = 24    # frozen copy of the seed-era threshold


class _SeedResourceCSR:
    """Frozen copy of the seed-era ``_ResourceCSR`` (vectorized frontier
    feasibility), so the comparator below stays independent of future
    changes to the live engine's batch-admission core."""

    def __init__(self, res_ids, num_res, caps):
        import numpy as np
        indptr = np.zeros(len(res_ids) + 1, dtype=np.int64)
        for i, ids in enumerate(res_ids):
            indptr[i + 1] = indptr[i] + len(ids)
        self.indptr = indptr
        self.flat = np.fromiter((r for ids in res_ids for r in ids),
                                dtype=np.int64, count=int(indptr[-1]))
        self.caps = np.asarray(caps, dtype=np.int64)

    def feasible(self, tasks, busy):
        import numpy as np
        rows = np.asarray(tasks, dtype=np.int64)
        starts = self.indptr[rows]
        lens = self.indptr[rows + 1] - starts
        total = int(lens.sum())
        if not total:
            return list(busy)
        gather = np.repeat(starts - np.cumsum(lens) + lens, lens) \
            + np.arange(total)
        counts = np.bincount(self.flat[gather], minlength=len(self.caps))
        new = np.asarray(busy, dtype=np.int64) + counts
        if np.any(new > self.caps):
            return None
        return new.tolist()


def _seed_generic_run(sim, tasks, total_blocks):
    """Frozen replica of the seed-era generic ``CompiledSim.run`` path (PR-4:
    per-call task interning, bitmap block coverage, blocking on every busy
    resource) — the comparator for the ``baseline`` cell. Kept verbatim
    (including its own copies of the batch threshold and CSR feasibility)
    so the cell keeps measuring the same thing as the engine evolves; its
    results are asserted bit-identical to the live engine before any
    speedup is reported, so semantic drift cannot hide here."""
    import heapq

    from repro.core.simulator import SimResult

    idx = sim.idx
    n = len(tasks)
    order = sorted(range(n), key=lambda i: tasks[i].priority)
    rank = [0] * n
    for pos, i in enumerate(order):
        rank[i] = pos

    ecache = {}
    res_ids = []
    durs = []
    nbytes = []
    dsts = []
    blks = []
    grps = []
    for t in tasks:
        e = (t.src, t.dst)
        ent = ecache.get(e)
        if ent is None:
            lat, bw = idx.edge_cost(e)
            ent = ecache[e] = (idx.edge_ids(e), lat, bw)
        ids, lat, bw = ent
        res_ids.append(ids)
        durs.append(lat + t.nbytes / bw)
        nbytes.append(t.nbytes)
        dsts.append(t.dst)
        blks.append(t.blk)
        grps.append(t.group)

    dep_left = [len(t.deps) for t in tasks]
    children = [None] * n
    for i, t in enumerate(tasks):
        for d in t.deps:
            c = children[d]
            if c is None:
                children[d] = [i]
            else:
                c.append(i)

    state = bytearray(n)
    ready = []
    for i in range(n):
        if not dep_left[i]:
            state[i] = 1
            ready.append((rank[i], i))
    heapq.heapify(ready)

    caps = idx.caps
    busy = [0] * idx.num_resources()
    res_wait = [None] * len(busy)
    nn = sim.topo.num_nodes
    root = sim.root
    remaining = [total_blocks] * nn
    remaining[root] = 0
    seen = [None] * nn
    node_finish = {root: 0.0}
    deliveries = []
    group_last = {}
    events = []
    seq = 0
    now = 0.0
    started = 0
    push = heapq.heappush
    pop = heapq.heappop
    deliver = deliveries.append
    csr = [None]

    def admit():
        nonlocal seq, started, busy
        if len(ready) >= _SEED_BATCH_MIN_READY:
            if csr[0] is None:
                csr[0] = _SeedResourceCSR(res_ids, len(busy), caps)
            batch = csr[0].feasible([i for _, i in ready], busy)
            if batch is not None:
                busy = batch
                for _, i in sorted(ready):
                    push(events, (now + durs[i], seq, i))
                    seq += 1
                    state[i] = 3
                started += len(ready)
                ready.clear()
                return
        while ready:
            _, i = pop(ready)
            if state[i] != 1:
                continue
            rs = res_ids[i]
            blocked = None
            for r in rs:
                if busy[r] >= caps[r]:
                    if blocked is None:
                        blocked = [r]
                    else:
                        blocked.append(r)
            if blocked is not None:
                state[i] = 2
                for r in blocked:
                    w = res_wait[r]
                    if w is None:
                        res_wait[r] = [i]
                    else:
                        w.append(i)
                continue
            for r in rs:
                busy[r] += 1
            push(events, (now + durs[i], seq, i))
            seq += 1
            started += 1
            state[i] = 3

    admit()
    completed = 0
    while events:
        now, _, i = pop(events)
        state[i] = 4
        completed += 1
        rs = res_ids[i]
        for r in rs:
            busy[r] -= 1
        d = dsts[i]
        rem = remaining[d]
        if rem > 0:
            sb = seen[d]
            if sb is None:
                sb = seen[d] = bytearray(total_blocks)
            fresh = 0
            for b in range(*blks[i]):
                if not sb[b]:
                    sb[b] = 1
                    fresh += 1
            if fresh:
                rem -= fresh
                remaining[d] = rem
                if rem <= 0 and d not in node_finish:
                    node_finish[d] = now
        deliver((now, nbytes[i]))
        g = grps[i]
        if g is not None:
            prev = group_last.get(g)
            if prev is None or now > prev:
                group_last[g] = now
        ch = children[i]
        if ch is not None:
            for j in ch:
                dl = dep_left[j] - 1
                dep_left[j] = dl
                if not dl and state[j] == 0:
                    state[j] = 1
                    push(ready, (rank[j], j))
        for r in rs:
            w = res_wait[r]
            if w is not None:
                res_wait[r] = None
                for j in w:
                    if state[j] == 2:
                        state[j] = 1
                        push(ready, (rank[j], j))
        admit()

    gf = [group_last[g] for g in sorted(group_last)] if group_last else []
    return SimResult(finish_time=max(node_finish.values()),
                     node_finish=node_finish, deliveries=deliveries,
                     group_finish=gf, started=started, completed=completed)


def _record(name: str, engine: str, topo: str, n: int, groups: int,
            tasks_per_s: float, speedup: float, **extra) -> None:
    _RECORDS.append(dict(name=name, engine=engine, topo=topo, n=n,
                         groups=groups, tasks_per_s=round(tasks_per_s),
                         speedup=round(speedup, 3), **extra))


def bench_engines(topo_name: str, n: int, groups: int, message_bytes: float,
                  repeats: int) -> dict:
    """Raw-loop and pipeline comparisons; returns the speedups by cell."""
    from repro.core import arborescence as arb
    from repro.core import topology as T
    from repro.core.fastsim import CompiledSim
    from repro.core.intersection import FULL_DUPLEX, ConflictModel
    from repro.core.schedule import build_pipeline
    from repro.core.simulator import EventSimulator, pipeline_tasks

    topo = T.by_name(topo_name, n)
    cm = ConflictModel(topo, FULL_DUPLEX)
    pipe = build_pipeline(topo, [arb.chain_arborescence(topo, 0)], cm)
    packet_bytes = [message_bytes / groups]
    tag = f"{topo_name}_{n}_m{groups}"
    out = {}

    # -- raw event loop on identical generic task lists ----------------------
    tasks = pipeline_tasks(pipe, packet_bytes, groups)
    ref_sim = EventSimulator(topo, cm, 0)
    fast_sim = CompiledSim(topo, cm, 0)
    t_ref = _best_of(lambda: ref_sim.run(tasks, total_blocks=groups), repeats)
    t_fast = _best_of(lambda: fast_sim.run(tasks, total_blocks=groups),
                      repeats)
    print(f"raw_run_reference_{tag},{t_ref * 1e6:.0f},"
          f"{len(tasks) / t_ref:.0f} tasks/s")
    print(f"raw_run_fast_{tag},{t_fast * 1e6:.0f},"
          f"{len(tasks) / t_fast:.0f} tasks/s")
    print(f"raw_run_speedup_{tag},{t_ref / t_fast:.2f},x")
    _record("raw_run", "reference", topo_name, n, groups,
            len(tasks) / t_ref, 1.0)
    _record("raw_run", "fast", topo_name, n, groups,
            len(tasks) / t_fast, t_ref / t_fast)
    out["raw_run"] = t_ref / t_fast

    # -- raw (non-analytic) pipeline event loop ------------------------------
    ref_full = ref_sim.run(pipeline_tasks(pipe, packet_bytes, groups),
                           total_blocks=groups)
    full_run = fast_sim.run_pipeline(pipe, packet_bytes, groups,
                                     max_sim_groups=None)
    assert full_run.res.finish_time == ref_full.finish_time \
        and full_run.res.deliveries == ref_full.deliveries \
        and full_run.res.node_finish == ref_full.node_finish, \
        "raw pipeline loop diverged from the reference oracle"

    def ref_e2e():
        ref_sim.run(pipeline_tasks(pipe, packet_bytes, groups),
                    total_blocks=groups)

    t_ref = _best_of(ref_e2e, repeats)
    t_fast = _best_of(lambda: fast_sim.run_pipeline(
        pipe, packet_bytes, groups, max_sim_groups=None), repeats)
    raw_speedup = t_ref / t_fast
    ntask = groups * len(pipe.flat_tasks())
    print(f"raw_pipeline_reference_{tag},{t_ref * 1e6:.0f},"
          f"{ntask / t_ref:.0f} tasks/s")
    print(f"raw_pipeline_fast_{tag},{t_fast * 1e6:.0f},"
          f"{ntask / t_fast:.0f} tasks/s (bit-identical full sim)")
    print(f"raw_pipeline_speedup_{tag},{raw_speedup:.2f},x")
    _record("raw_pipeline", "reference", topo_name, n, groups,
            ntask / t_ref, 1.0)
    _record("raw_pipeline", "fast", topo_name, n, groups,
            ntask / t_fast, raw_speedup)
    out["raw_pipeline"] = raw_speedup

    # -- end-to-end pipelined broadcast (analytics on) -----------------------
    fast_run = [None]

    def fast_e2e():
        fast_run[0] = fast_sim.run_pipeline(pipe, packet_bytes, groups,
                                            max_sim_groups=6)

    t_fast = _best_of(fast_e2e, repeats)
    run = fast_run[0]
    err = abs(run.res.finish_time - ref_full.finish_time) \
        / ref_full.finish_time
    assert err < 1e-9, f"engines disagree: rel err {err:.2e}"
    speedup = t_ref / t_fast
    print(f"pipeline_fast_{tag},{t_fast * 1e6:.0f},"
          f"steady={run.steady} sim_groups={run.sim_groups}")
    print(f"pipeline_speedup_{tag},{speedup:.2f},x (finish rel err {err:.1e})")
    _record("pipeline", "fast", topo_name, n, groups, ntask / t_fast,
            speedup, steady=run.steady, finish_rel_err=err)
    out["pipeline"] = speedup
    return out


def bench_baselines(topo_name: str, n: int, message_bytes: float,
                    repeats: int) -> float:
    """The routed-baseline raw loop: memoized lowering + folded/generic
    engine (what ``simulate_baseline`` runs today) vs the seed-era per-call
    path (task generation + ``_seed_generic_run``). Bit-identity against the
    reference oracle is asserted per algorithm before timing; the timing is
    CPU-time with interleaved repeats. Returns the geometric-mean speedup
    (the gated headline); per-algorithm records land in the JSON."""
    import math

    from repro.core import topology as T
    from repro.core.baselines import BASELINES, lower_baseline
    from repro.core.fastsim import CompiledSim
    from repro.core.intersection import FULL_DUPLEX, ConflictModel
    from repro.core.simulator import EventSimulator

    topo = T.by_name(topo_name, n)
    cm = ConflictModel(topo, FULL_DUPLEX)
    sim = CompiledSim(topo, cm, 0)
    ref_sim = EventSimulator(topo, cm, 0)
    algos = ("srda", "pipeline", "bine", "glf")
    speedups = []
    for algo in algos:
        tasks = BASELINES[algo](topo, 0, message_bytes)
        tb = max(t.blk[1] for t in tasks)
        ref = ref_sim.run(tasks, total_blocks=tb)
        ctl = lower_baseline(topo, cm, algo, 0, message_bytes)
        fast = sim.run_lowered(ctl)
        seed = _seed_generic_run(sim, tasks, tb)
        for got, engine in ((fast, "lowered"), (seed, "seed replica")):
            assert got.finish_time == ref.finish_time \
                and got.node_finish == ref.node_finish \
                and got.deliveries == ref.deliveries, \
                f"baseline {algo}: {engine} path diverged from the oracle"

        def run_seed():
            ts = BASELINES[algo](topo, 0, message_bytes)
            _seed_generic_run(sim, ts, tb)

        def run_fast():
            sim.run_lowered(lower_baseline(topo, cm, algo, 0, message_bytes))

        t_seed, t_fast = _best_of_cpu_interleaved([run_seed, run_fast],
                                                  repeats)
        speedup = t_seed / t_fast
        speedups.append(speedup)
        tag = f"{topo_name}_{n}_{algo}"
        folded = bool(ctl.seg is not None and ctl.seg.foldable)
        print(f"baseline_seed_{tag},{t_seed * 1e6:.0f},"
              f"{len(tasks) / t_seed:.0f} tasks/s")
        print(f"baseline_fast_{tag},{t_fast * 1e6:.0f},"
              f"{len(tasks) / t_fast:.0f} tasks/s (bit-identical; "
              f"folded={folded})")
        print(f"baseline_speedup_{tag},{speedup:.2f},x")
        _record("baseline", "fast", topo_name, n, 0, len(tasks) / t_fast,
                speedup, algo=algo, folded=folded, n_tasks=len(tasks))
    geomean = math.exp(sum(math.log(s) for s in speedups) / len(speedups))
    print(f"baseline_speedup_geomean_{topo_name}_{n},{geomean:.2f},x")
    _record("baseline_geomean", "fast", topo_name, n, 0, 0.0, geomean,
            algos=list(algos))
    return geomean


def bench_kernel_sweep(topo_name: str, n: int, repeats: int) -> float:
    """The kernel engine's adaptive dispatch on a grid-sweep row vs the
    generic round loop on the same lowered lists (see the module
    docstring). Returns the gated aggregate tasks/s ratio."""
    import copy

    from repro.core import kernelsim as KS
    from repro.core import topology as T
    from repro.core.baselines import lower_baseline
    from repro.core.fastsim import CompiledSim
    from repro.core.intersection import FULL_DUPLEX, ConflictModel

    topo = T.by_name(topo_name, n)
    cm = ConflictModel(topo, FULL_DUPLEX)
    sim = CompiledSim(topo, cm, 0)
    ks = KS.KernelSim(topo, cm, 0)
    families = ("binomial", "srda", "glf", "bine", "pipeline")
    sizes = (4e6, 64e6)
    cells = []                       # (family, ctl, generic-forced copy)
    n_tasks = 0
    for fam in families:
        for size in sizes:
            ctl = lower_baseline(topo, cm, fam, 0, size)
            cc = copy.copy(ctl)
            cc.seg = None            # the pre-fold generic round loop
            cc._tpl = None
            rk = ks.run_lowered(ctl)
            rg = sim.run_lowered(cc)
            assert rk.finish_time == rg.finish_time \
                and rk.node_finish == rg.node_finish \
                and rk.deliveries == rg.deliveries, \
                f"kernel_sweep {fam}@{size:.0e}: engines diverged"
            cells.append((fam, ctl, cc))
            n_tasks += ctl.n

    def run_kernel():
        for _, ctl, _ in cells:
            ks.run_lowered(ctl)

    def run_generic():
        for _, _, cc in cells:
            sim.run_lowered(cc)

    t_gen, t_ker = _best_of_cpu_interleaved([run_generic, run_kernel],
                                            repeats)
    speedup = t_gen / t_ker
    tag = f"{topo_name}_{n}"
    # per-family components (single timed pass, transparency only): the
    # aggregate win is dominated by the chain-family fold; the flat
    # families run the same generic numpy loop on this 1-core host
    for fam in families:
        fs = [c for c in cells if c[0] == fam]
        t0 = time.process_time()
        for _, ctl, _ in fs:
            ks.run_lowered(ctl)
        tk = time.process_time() - t0
        t0 = time.process_time()
        for _, _, cc in fs:
            sim.run_lowered(cc)
        tg = time.process_time() - t0
        folded = bool(fs[0][1].seg is not None and fs[0][1].seg.foldable)
        print(f"kernel_sweep_{tag}_{fam},{tg / max(tk, 1e-12):.2f},x "
              f"(folded={folded})")
    print(f"kernel_sweep_generic_{tag},{t_gen * 1e6:.0f},"
          f"{n_tasks / t_gen:.0f} tasks/s")
    print(f"kernel_sweep_kernel_{tag},{t_ker * 1e6:.0f},"
          f"{n_tasks / t_ker:.0f} tasks/s (bit-identical)")
    print(f"kernel_sweep_speedup_{tag},{speedup:.2f},x")
    _record("kernel_sweep", "kernel", topo_name, n, 0, n_tasks / t_ker,
            speedup, families=list(families), sizes=list(sizes),
            n_tasks=n_tasks)
    return speedup


def bench_churn(topo_name: str, n: int, message_bytes: float) -> None:
    """Degradation under a single mid-broadcast link kill: clean vs faulty
    finish time, T(m) overhead, repair latency and retry count for the srda
    baseline. Engine parity on the repaired run is asserted before
    recording. Reported, not gated: there is no committed floor for this
    cell (overhead is a model property, not a perf number)."""
    from repro import api
    from repro.core import topology as T
    from repro.core.baselines import BASELINES
    from repro.core.faults import FaultSchedule, verify_delivery
    from repro.core.simconfig import SimConfig

    topo = T.by_name(topo_name, n)
    model = api.compile(topo)
    algo = "srda"
    clean = model.simulate_baseline(algo, 0, message_bytes)
    edges = sorted({(t.src, t.dst)
                    for t in BASELINES[algo](topo, 0, message_bytes)})
    u, v = edges[len(edges) // 2]
    sched = FaultSchedule.kill_edge(topo, u, v, 0.45 * clean.finish_time)
    faulty = model.simulate_baseline(
        algo, 0, message_bytes,
        config=SimConfig(engine="fast", faults=sched))
    ref = model.simulate_baseline(
        algo, 0, message_bytes,
        config=SimConfig(engine="reference", faults=sched))
    assert faulty.finish_time == ref.finish_time \
        and faulty.faults == ref.faults, \
        "churn: engines diverged on the repaired run"
    assert verify_delivery(topo, sched, faulty, 0).ok, \
        "churn: delivery verification failed"
    fr = faulty.faults
    overhead = faulty.finish_time - clean.finish_time
    tag = f"{topo_name}_{n}_{algo}"
    print(f"churn_clean_{tag},{clean.finish_time * 1e6:.1f},us")
    print(f"churn_faulty_{tag},{faulty.finish_time * 1e6:.1f},us "
          f"(overhead {overhead / clean.finish_time * 100:+.1f}%)")
    print(f"churn_repair_latency_{tag},{fr.repair_latency * 1e6:.1f},us "
          f"(retries={fr.retries} repair_tasks={fr.repair_tasks})")
    _record("churn", "fast", topo_name, n, 0, 0.0, 1.0, algo=algo,
            t_clean=clean.finish_time, t_faulty=faulty.finish_time,
            overhead=overhead, repair_latency=fr.repair_latency,
            retries=fr.retries, repair_tasks=fr.repair_tasks,
            lost=len(fr.lost))


def bench_cycle(repeats: int) -> None:
    """Verified occupancy-cycle path on a jittery schedule (two_tree on the
    all-port ring16): the detector must fire and match the full run."""
    from repro.core import arborescence as arb
    from repro.core import topology as T
    from repro.core.fastsim import CompiledSim
    from repro.core.intersection import ALL_PORT, ConflictModel
    from repro.core.schedule import build_pipeline

    topo = T.ring(16)
    cm = ConflictModel(topo, ALL_PORT)
    pipe = build_pipeline(topo, arb.two_tree(topo, 0), cm)
    packet_bytes = [2e5 * t.weight for t in pipe.trees]
    m = 1000
    sim = CompiledSim(topo, cm, 0)
    full = sim.run_pipeline(pipe, packet_bytes, m, max_sim_groups=None)
    run = sim.run_pipeline(pipe, packet_bytes, m, max_sim_groups=6,
                           cycle_scan_groups=192)
    assert run.cycle is not None and run.cycle.verified, \
        "occupancy-cycle detector failed to fire on ring16 two_tree"
    err = abs(run.res.finish_time - full.res.finish_time) \
        / full.res.finish_time
    assert err < 1e-9, f"cycle path inexact: rel err {err:.2e}"
    t_full = _best_of(lambda: sim.run_pipeline(
        pipe, packet_bytes, m, max_sim_groups=None), repeats)
    t_cycle = _best_of(lambda: sim.run_pipeline(
        pipe, packet_bytes, m, max_sim_groups=6, cycle_scan_groups=192),
        repeats)
    ntask = m * len(pipe.flat_tasks())
    print(f"cycle_full_ring16_m{m},{t_full * 1e6:.0f},us")
    print(f"cycle_analytic_ring16_m{m},{t_cycle * 1e6:.0f},"
          f"p={run.cycle.period} start={run.cycle.start} rel_err={err:.1e}")
    print(f"cycle_speedup_ring16_m{m},{t_full / t_cycle:.2f},x")
    _record("cycle", "fast", "ring", 16, m, ntask / t_cycle,
            t_full / t_cycle, period=run.cycle.period,
            finish_rel_err=err)


def bench_build_plan(topo_name: str, n: int) -> None:
    from repro.core import topology as T
    from repro.core.bbs import build_plan

    topo = T.by_name(topo_name, n)
    t0 = time.perf_counter()
    plan = build_plan(topo, root=0)
    dt = time.perf_counter() - t0
    hints = sum(1 for c in plan.candidates if c.cycle is not None)
    print(f"build_plan_{topo_name}_{n},{dt * 1e6:.0f},"
          f"{len(plan.candidates)} candidates; {hints} cycle hints")
    _record("build_plan", "fast", topo_name, n, 0, 0.0, 1.0,
            seconds=round(dt, 4), candidates=len(plan.candidates),
            cycle_hints=hints)


def bench_plan_cache(n: int, requests: int = 100) -> None:
    """Symmetry-orbit plan sharing + the warm plan service (see module
    docstring). Speedup = extrapolated per-root build cost over the
    measured orbit-shared pack assembly (builds + relabels + pickling)."""
    import tempfile

    from repro.core import topology as T
    from repro.core.bbs import broadcast_time, build_plan
    from repro.core.planstore import PlanStore
    from repro.launch.planserver import PlanServer

    server_topo = None
    for topo_name in ("mesh2d", "torus2d"):
        topo = T.by_name(topo_name, n)
        nn = topo.num_nodes
        orbits = topo.automorphisms().orbits()
        k = orbits.num_orbits

        # per-root cost: sample a few spread-out roots, extrapolate to n
        sample = sorted({0, nn // 3, (2 * nn) // 3})
        per = []
        for r in sample:
            t0 = time.perf_counter()
            build_plan(topo, root=r)
            per.append(time.perf_counter() - t0)
        per_root_est = sum(per) / len(per) * nn

        # orbit-shared: the packed artifact over every root (k builds,
        # n - k witness relabels, one pickle to disk)
        with tempfile.TemporaryDirectory() as d:
            store = PlanStore(d)
            t0 = time.perf_counter()
            plans, _, _ = store.get_or_build_packed(topo, roots=range(nn))
            orbit_wall = time.perf_counter() - t0
        speedup = per_root_est / orbit_wall

        # relabeled plans must answer exactly like fresh builds
        probe_root = nn - 1
        fresh = build_plan(topo, root=probe_root)
        for M in (1e6, 16e6):
            tp, _ = broadcast_time(plans[probe_root], M)
            tf, _ = broadcast_time(fresh, M)
            assert tp == tf, \
                f"plan_cache {topo_name}: relabeled plan diverged at " \
                f"root {probe_root}, M={M:g} ({tp} != {tf})"

        tag = f"{topo_name}_{nn}"
        print(f"plan_cache_per_root_est_{tag},{per_root_est * 1e6:.0f},"
              f"us for {nn} roots (sampled {len(sample)})")
        print(f"plan_cache_orbit_{tag},{orbit_wall * 1e6:.0f},"
              f"us ({k} orbit build(s) + {nn - k} relabels)")
        print(f"plan_cache_speedup_{tag},{speedup:.2f},x")
        _record("plan_cache", "fast", topo_name, nn, 0, 0.0, speedup,
                orbits=k, builds=k, relabels=nn - k,
                per_root_est_s=round(per_root_est, 4),
                orbit_wall_s=round(orbit_wall, 4))
        if topo_name == "torus2d":
            server_topo = topo

    # warm plan service over the vertex-transitive fabric: a request
    # stream cycling through every (symmetric) root must stay warm
    server = PlanServer()
    fp = server.register(server_topo)
    nn = server_topo.num_nodes
    sizes = (64e3, 1e6, 4e6, 16e6)
    t0 = time.perf_counter()
    for i in range(requests):
        server.request(fp, i % nn, sizes[i % len(sizes)])
    serve_wall = time.perf_counter() - t0
    st = server.stats
    print(f"plan_cache_hit_rate_torus2d_{nn},{st.hit_rate:.3f},"
          f"{requests} requests: {st.builds} build(s) "
          f"{st.relabels} relabel(s) {st.l1_hits} L1 hits "
          f"({serve_wall:.2f}s wall)")
    _record("plan_cache_hit_rate", "fast", "torus2d", nn, 0, 0.0, 1.0,
            hit_rate=round(st.hit_rate, 4), requests=requests,
            builds=st.builds, relabels=st.relabels, l1_hits=st.l1_hits,
            build_seconds=round(st.build_seconds, 4),
            relabel_seconds=round(st.relabel_seconds, 4))


def bench_workload(n: int) -> None:
    """Concurrent multi-root broadcast workloads (``repro.workload``): a
    deterministic fixed-seed offered-load sweep on the mesh2d fabric,
    roots restricted to one corner orbit (one canonical plan build serves
    all four roots through the PlanServer). The gated cell is the
    *sustained* jobs/s at the heaviest offered point — deep past the
    saturation knee, so it measures fabric capacity in simulated time
    (deterministic, machine-independent); wall-clock engine throughput is
    recorded as context, never gated."""
    import math

    from repro import api
    from repro.core import topology as T
    from repro.workload import offered_load_sweep, poisson_jobs, \
        run_workload, saturation_point

    topo = T.by_name("mesh2d", n)
    cols = int(math.isqrt(n))
    roots = [0, cols - 1, n - cols, n - 1]        # the corner orbit
    model = api.compile(topo, server=True)
    nbytes = 1e6
    t1, _ = model.broadcast_time(0, nbytes)
    base = 1.0 / t1                               # 1 job per isolated T(M)

    mults = (0.25, 1.0, 4.0, 16.0)
    num_jobs = 32
    reps = offered_load_sweep(model, [m * base for m in mults],
                              num_jobs=num_jobs, roots=roots,
                              nbytes=nbytes, seed=20260809)
    tag = f"mesh2d_{n}"
    for mult, rep in zip(mults, reps):
        print(f"workload_{tag}_x{mult:g},{rep.jobs_per_s:.0f},"
              f"jobs/s sustained (offered {rep.offered_rate:.0f}, "
              f"p99 {rep.latency_p99 * 1e6:.0f}us, "
              f"q99 {rep.queue_p99 * 1e6:.0f}us, sat={rep.saturated})")
    sat = saturation_point(reps)
    heavy = reps[-1]
    assert heavy.saturated, \
        "workload cell: heaviest offered point failed to saturate"
    assert model.server.stats.builds == 1, \
        "workload cell: corner orbit took more than one plan build"

    # wall-clock engine throughput (context only; simulated-time cells gate)
    jobs = poisson_jobs(mults[-1] * base, num_jobs, roots, nbytes,
                        seed=20260809)
    t0 = time.perf_counter()
    rep2 = run_workload(model, jobs)
    wall = time.perf_counter() - t0
    assert rep2.to_dict() == heavy.to_dict(), \
        "workload cell: rerun diverged — workload is not deterministic"
    print(f"workload_saturation_{tag},{heavy.jobs_per_s:.0f},"
          f"jobs/s capacity (knee at {sat if sat else 0:.0f} offered; "
          f"{rep2.completed / wall:.0f} tasks/s wall)")
    _record("workload", "fast", "mesh2d", n, 0,
            rep2.completed / wall, 1.0,
            jobs_per_s=round(heavy.jobs_per_s, 1),
            offered_rate=round(heavy.offered_rate, 1),
            latency_p99=heavy.latency_p99,
            queue_p99=heavy.queue_p99,
            saturation_offered=round(sat, 1) if sat else None,
            num_jobs=num_jobs, nbytes=nbytes)


def bench_device(smoke: bool) -> None:
    """Device-collective cell: run the compiled BBS plan on an emulated
    8-device mesh, fit the Hockney calibration, and record measured cycle
    throughput plus predicted-vs-measured cycle-time error.

    Runs in a subprocess (the main bench process must keep one device;
    ``XLA_FLAGS`` only takes effect before jax initializes). Also writes
    the ``CalibratedCost`` JSON artifact consumed by roofline.py. Delivery
    is asserted bit-exact before any timing — a fast wrong answer must
    never post a throughput number."""
    import subprocess
    import textwrap

    # reps stays at 5 in both profiles: the cell gates a prediction-error
    # ceiling, and min-of-reps is the noise control on a shared runner
    iters, reps = (16, 5) if smoke else (32, 5)
    art = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "artifacts")
    os.makedirs(art, exist_ok=True)
    cal_path = os.path.join(art, "calibration.json")
    code = textwrap.dedent(f"""
        import json, sys, warnings
        warnings.filterwarnings('ignore', message='.*donated.*')
        import numpy as np, jax.numpy as jnp
        from repro import api
        from repro.core import topology as T
        from repro.device import calibrate, prediction_report
        # 4 MiB => a deep pipeline (m ~ 9 groups), so steady-state cycle
        # cost dominates the fixed dispatch overhead the Hockney model
        # does not cover
        topo = T.ring(8)
        model = api.compile(topo)
        ex = model.executable(root=0, nbytes=4 << 20)
        mesh = ex.mesh()
        x = jnp.asarray(np.random.RandomState(0)
                        .rand(1 << 20).astype(np.float32))
        chk = ex.verify(x, mesh)
        assert chk.ok, f'delivery failed on devices {{chk.missing}}'
        cost = calibrate(topo, mesh,
                         sizes=(8 << 10, 64 << 10, 256 << 10, 1 << 20),
                         iters={iters}, reps={reps})
        cost.save({cal_path!r})
        r = prediction_report([ex], cost, mesh=mesh, reps={reps})[0]
        cls = next(iter(cost.classes))
        json.dump(dict(cycles_per_s=1.0 / r.measured_cycle_s,
                       pred_err=r.rel_err, candidate=r.candidate,
                       num_cycles=r.num_cycles, alpha=cost.alpha(cls),
                       beta=cost.beta(cls)), sys.stdout)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # this parent has already run jax cells; on a TPU host it holds the
    # chips, so the emulated mesh stays on the host CPU
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device_collective subprocess failed:\n{proc.stderr}")
    res = json.loads(proc.stdout)
    print(f"device_collective_ring8,{res['cycles_per_s']:.0f},"
          f"cycles/s emulated ({res['candidate']}, "
          f"pred_err {100 * res['pred_err']:.1f}%, "
          f"alpha {res['alpha'] * 1e6:.1f}us, "
          f"beta {res['beta'] / 1e9:.2f}GB/s)")
    _record("device_collective", "device", "ring", 8, 0,
            0.0, 1.0, cycles_per_s=round(res["cycles_per_s"], 1),
            pred_err=round(res["pred_err"], 4),
            candidate=res["candidate"], num_cycles=res["num_cycles"],
            alpha=res["alpha"], beta=res["beta"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small topology, quick run (perf-regression smoke)")
    ap.add_argument("--topo", default="mesh2d")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--groups", type=int, default=16)
    ap.add_argument("--message", type=float, default=16e6)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", default="BENCH_simbench.json",
                    help="machine-readable results path ('' disables); "
                         "gate it with benchmarks.check_regression (one "
                         "gate implementation, committed floors)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    n = args.n or (64 if args.smoke else 256)
    bench_engines(args.topo, n, args.groups, args.message, args.repeats)
    bench_baselines(args.topo, n, args.message, args.repeats)
    bench_kernel_sweep(args.topo, n, args.repeats)
    bench_churn(args.topo, 64 if args.smoke else n, args.message)
    bench_cycle(args.repeats)
    bench_build_plan(args.topo, 64 if args.smoke else 128)
    bench_plan_cache(64 if args.smoke else 256)
    bench_workload(64 if args.smoke else 256)
    bench_device(args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "simbench",
                       "smoke": bool(args.smoke),
                       "created": time.time(),
                       "records": _RECORDS}, f, indent=1)
        print(f"# wrote {os.path.abspath(args.json)}", file=sys.stderr)
    # gating lives in exactly one place: benchmarks/check_regression.py
    # against the committed floors (see `make bench` / `make bench-smoke`)
    return 0


if __name__ == "__main__":
    sys.exit(main())
