"""JIT-kernelized round engine over lowered task lists.

``KernelSim`` executes a ``repro.core.routing.CompiledTaskList`` through a
jax-jitted event core instead of the Python event loop in
``repro.core.fastsim``. The jitted core consumes the lowered arrays
directly — admission ranks, the padded dense resource matrix (the CSR rows
right-padded to one width), Hockney durations, the padded dependency
matrix — and replays the reference engine's exact schedule.

Park-free reformulation
-----------------------
The numpy loop's parked/wake bookkeeping exists to avoid rescanning the
ready set; it never changes *which* tasks admit. At any moment a task
admits iff its dependencies are complete, it has not started, and every
resource on its row is below capacity — all properties of (completion
set, occupancy), never of the parking bookkeeping. The kernel therefore
keeps only task status (unstarted / running / done, a single padded int8
vector that doubles as the dependency-satisfaction table) plus occupancy,
and alternates two guarded step types inside one ``lax.while_loop``:

  * if any task is admissible, admit the minimum-rank one — the
    reference's rank-ordered greedy admission, re-evaluated after every
    admission because occupancy only grows within an event — assigning
    the next admission sequence number and ``finish = now + dur``;
  * otherwise complete the earliest ``(finish, seq)`` running task (the
    reference heap's pop key) and re-evaluate.

A task the reference parks is simply one that fails the occupancy test:
the reference reconsiders it only when its parked resource frees, but
between those events that resource stays full, so the occupancy test
fails exactly while the reference would not look. Admission order, seq
numbers, and the interleaving around tied completion times all coincide
(admission always preferred over the next completion, as in the
reference's admit-after-every-pop loop), and the loop runs the same IEEE
double expressions as the numpy engine, so event times are bit-identical,
not merely close; tests assert exact equality and the acceptance bound of
<= 1e-9 relative on T(m) is pure headroom. Each run takes exactly ``2n``
loop iterations (n admissions + n completions) — no wake thrash, which is
what makes the core vmap cleanly: lanes stay in lockstep.

Coverage, node finish times, deliveries and group finishes are *not*
tracked inside the jitted loop — they are pure functions of the per-task
completion times and admission sequence numbers, recovered vectorized
afterwards (``_postprocess``).

Dispatch policy
---------------
The numpy engine remains the always-available fallback and the exactness
oracle. ``KernelSim`` routes every run to the fastest bit-identical path
for the host:

  * fold-eligible lists (``ctl.seg.foldable`` — the chain family and
    srda's ring allgather) go to the numpy folded instance core: the fold
    collapses per-instance work that the flat kernel would replay task by
    task, and it is the proven-identical engine path;
  * fault schedules, the segment-analytic ``run_task_list`` path for
    foldable lists, and empty lists delegate to ``CompiledSim``;
  * everything else (the un-foldable flat lists the generic round loop
    would run) uses the jitted core when the jit policy says it pays:
    always when ``REPRO_KERNEL_JIT=1``/``force`` or ``jit=True`` is
    passed, never when ``REPRO_KERNEL_JIT=0``/``off`` or jax is missing,
    and by default only when jax sees more than one host CPU device — on
    a single-core CPU host the XLA loop's per-step op dispatch makes it
    ~0.5x the tuned numpy loop, while lane batching across devices
    amortizes it into a win; the numpy path is bit-identical either way,
    so the policy is a pure performance choice.

The jitted core always runs on the host CPU (``_core_device``), whatever
accelerator the process holds: its float64 event times are exact only
where float64 is IEEE double, and a TPU's is not. Accelerator chips
therefore never count towards the jit policy.

``run_lowered_batch`` vmaps the core across message-size lanes that share
one lowered structure (same tasks, ranks, resources, dependencies — only
durations and payload bytes differ), so a whole grid-sweep row costs one
dispatch; with the jit policy off it runs the lanes through the numpy
engine one by one, same results. ``benchmarks/gridsweep.py`` and the
``kernel`` simbench cell are built on it.
"""

from __future__ import annotations

import copy
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.fastsim import CompiledSim
from repro.core.intersection import ConflictModel
from repro.core.routing import CompiledTaskList
from repro.core.simulator import SimResult
from repro.core.topology import Topology

try:                                      # CPU jit (see _core_device)
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    KERNEL_AVAILABLE = True
except Exception:                         # pragma: no cover - jax baked in
    jax = None
    jnp = None
    lax = None
    KERNEL_AVAILABLE = False


# completions cannot tie on (time, seq): seq is unique, so this sentinel
# only pads the masked argmins
_BIG_SEQ = np.int32(2 ** 31 - 1)


def _jit_default() -> bool:
    """Whether the jitted core is the profitable path on this host (see
    the module docstring's dispatch policy)."""
    env = os.environ.get("REPRO_KERNEL_JIT", "").lower()
    if env in ("1", "force", "on"):
        return True
    if env in ("0", "off"):
        return False
    return KERNEL_AVAILABLE and len(jax.devices("cpu")) > 1


def _core_device():
    """The device the jitted core runs on: the host CPU, always.

    XLA:TPU has no IEEE float64; it emulates it with pairs of float32.
    On a TPU v5e the core's event times came out up to 6.8e-15 relative
    off the numpy engine (binomial on mesh2d 16x16 at 64e6 bytes), where
    the contract is bit-identical. On the CPU they are identical."""
    return jax.devices("cpu")[0]


def _core(rank, res, caps, deps, durs):
    """One lane of the jitted event core (see the module docstring for the
    park-free equivalence argument).

    Shapes (all static): ``rank`` i32[n] (unique admission permutation),
    ``res`` i32[n, K] padded with the dummy resource id R (``caps`` is
    i32[R+1] with a huge dummy capacity), ``deps`` i32[n, D] padded with n
    (``status`` carries a sentinel done slot at index n), ``durs`` f64[n].
    Returns per-task completion times f64[n] and admission sequence
    numbers i32[n].
    """
    n = rank.shape[0]
    inf = jnp.float64(np.inf)

    def cond(st):
        return st[-1] < n

    def body(st):
        status, busy, fin, seqs, comp, ctr, now, ncomp = st
        # status: 0 unstarted, 1 running, 2 done; slot n = done sentinel,
        # so the padded dependency rows read as satisfied
        dep_done = (status[deps] == 2).all(axis=1)
        free = busy < caps
        adm = dep_done & (status[:n] == 0) & free[res].all(axis=1)
        i = jnp.argmin(jnp.where(adm, rank, n))
        any_adm = adm[i]

        # admission effects (no-ops when nothing is admissible)
        status = status.at[i].set(
            jnp.where(any_adm, 1, status[i]).astype(jnp.int8))
        # masked scatter-adds keep the occupancy buffer aliased through
        # the loop — a where() over the whole vector would copy it
        busy = busy.at[res[i]].add(jnp.where(any_adm, 1, 0))
        fin = fin.at[i].set(jnp.where(any_adm, now + durs[i], fin[i]))
        seqs = seqs.at[i].set(jnp.where(any_adm, ctr, seqs[i]))
        ctr = ctr + jnp.where(any_adm, 1, 0)

        # completion effects (the reference heap pop, when no admission)
        g = ~any_adm
        m = jnp.min(fin)
        j = jnp.argmin(jnp.where(fin == m, seqs, _BIG_SEQ))
        now = jnp.where(g, m, now)
        comp = comp.at[j].set(jnp.where(g, m, comp[j]))
        fin = fin.at[j].set(jnp.where(g, inf, fin[j]))
        status = status.at[j].set(
            jnp.where(g, 2, status[j]).astype(jnp.int8))
        busy = busy.at[res[j]].add(jnp.where(g, -1, 0))
        ncomp = ncomp + jnp.where(g, 1, 0)
        return status, busy, fin, seqs, comp, ctr, now, ncomp

    nres = caps.shape[0]
    st = (jnp.zeros(n + 1, dtype=jnp.int8).at[n].set(2),
          jnp.zeros(nres, dtype=jnp.int32),
          jnp.full(n, np.inf, dtype=jnp.float64),
          jnp.full(n, _BIG_SEQ, dtype=jnp.int32),
          jnp.zeros(n, dtype=jnp.float64),
          jnp.int32(0),
          jnp.float64(0.0),
          jnp.int32(0))
    st = lax.while_loop(cond, body, st)
    return st[4], st[3]


if KERNEL_AVAILABLE:
    _CORE = jax.jit(_core)
    _CORE_BATCH = jax.jit(jax.vmap(
        _core, in_axes=(None, None, None, None, 0)))


def _static_arrays(ctl: CompiledTaskList, idx) -> Tuple[np.ndarray, ...]:
    """Pad the lowered CSR into the fixed-width matrices the core consumes
    (lane-independent structure: ranks, resources, dependencies)."""
    n = ctl.n
    rank = np.asarray(ctl.rank, dtype=np.int32)
    # compact the dense ids to the resources this list actually touches:
    # the occupancy vector is a loop carry, so its width is per-iteration
    # memory traffic
    used = np.unique(np.asarray(ctl.res_flat, dtype=np.int64))
    remap = {int(r): k for k, r in enumerate(used)}
    nres = used.size
    K = max(1, max((len(r) for r in ctl.res_ids), default=1))
    res = np.full((n, K), nres, dtype=np.int32)
    for i, rs in enumerate(ctl.res_ids):
        res[i, :len(rs)] = [remap[r] for r in rs]
    caps = np.empty(nres + 1, dtype=np.int64)
    caps[:nres] = np.asarray(idx.caps, dtype=np.int64)[used]
    caps[nres] = 2 ** 30              # the dummy pad id never contends
    D = max(1, max(ctl.dep_n, default=1))
    deps = np.full((n, D), n, dtype=np.int32)   # n = always-done sentinel
    for i, ds in enumerate(ctl.deps):
        deps[i, :len(ds)] = ds
    return rank, res, caps.astype(np.int32), deps


class KernelSim:
    """Drop-in engine: ``run``/``run_lowered`` like ``CompiledSim``, the
    event core jitted; plus ``run_lowered_batch`` for vmapped lanes.

    Capability gates delegate to the numpy engine (the exactness oracle):
    fault schedules, foldable lists (the folded instance core is the
    proven-identical fast path), the segment-analytic ``run_task_list``
    machinery, empty lists, and any environment without jax fall back to
    ``CompiledSim`` bit-identically. The ``jit`` keyword (default: the
    ``REPRO_KERNEL_JIT``/device-count policy in the module docstring)
    picks the execution path for everything else; the jitted core runs
    on the host CPU (``_core_device``).
    """

    def __init__(self, topo: Topology, cm: ConflictModel, root: int):
        self.topo = topo
        self.cm = cm
        self.root = root
        self._np = CompiledSim(topo, cm, root)
        self.idx = self._np.idx

    # CompiledSim surface used by the entrypoints -------------------------
    def lower(self, tasks, total_blocks=None):
        return self._np.lower(tasks, total_blocks)

    def run(self, tasks, total_blocks=None, faults=None,
            jit: Optional[bool] = None) -> SimResult:
        if faults:
            # fault events invalidate the static lowering the kernel
            # consumes; the numpy fault loop is the engine for churn
            return self._np.run(tasks, total_blocks, faults=faults)
        return self.run_lowered(self._np.lower(tasks, total_blocks),
                                jit=jit)

    def run_task_list(self, tasks=None, *, lowered=None,
                      total_blocks=None, max_sim_segments=None,
                      jit: Optional[bool] = None, **kw):
        ctl = (lowered if lowered is not None
               else self._np.lower(tasks, total_blocks))
        seg = ctl.seg
        if seg is not None and seg.foldable:
            # the segment analytics (verified occupancy cycles) and the
            # folded core are numpy paths; exactness there is the folded
            # loop's concern, not the kernel's
            return self._np.run_task_list(
                None, lowered=ctl, max_sim_segments=max_sim_segments, **kw)
        from repro.core.fastsim import TaskListRun
        return TaskListRun(res=self.run_lowered(ctl, jit=jit),
                           sim_segments=0, delta=0.0)

    # the kernel path -----------------------------------------------------
    def run_lowered(self, ctl: CompiledTaskList,
                    jit: Optional[bool] = None) -> SimResult:
        seg = ctl.seg
        if seg is not None and seg.foldable:
            return self._np.run_lowered(ctl)
        use_jit = _jit_default() if jit is None else jit
        if not KERNEL_AVAILABLE or not use_jit or ctl.n == 0:
            return self._np.run_lowered(ctl)
        ctl.bind(self.idx)
        stat = _static_arrays(ctl, self.idx)
        durs = np.asarray(ctl.durs, dtype=np.float64)
        comp, seqs = _CORE(*jax.device_put((*stat, durs), _core_device()))
        return self._postprocess(ctl, np.asarray(comp),
                                 np.asarray(seqs, dtype=np.int64))

    def run_lowered_batch(self, ctl: CompiledTaskList,
                          durs_lanes: np.ndarray,
                          nbytes_lanes: Optional[np.ndarray] = None,
                          jit: Optional[bool] = None) -> List[SimResult]:
        """Run ``L`` message-size lanes of one lowered structure.

        ``durs_lanes`` is ``[L, n]`` float64 — each lane's Hockney
        durations over the *same* task list (same ranks, resources,
        dependencies, block structure). ``nbytes_lanes`` optionally scales
        each lane's per-task payload bytes for the delivery records
        (defaults to ``ctl.nbytes`` for every lane). With the jit policy
        on, all lanes go through one vmapped dispatch; otherwise each lane
        runs through the numpy engine on a per-lane rebind of the shared
        structure — bit-identical either way."""
        durs_lanes = np.asarray(durs_lanes, dtype=np.float64)
        L, n = durs_lanes.shape
        assert n == ctl.n
        use_jit = _jit_default() if jit is None else jit
        foldable = ctl.seg is not None and ctl.seg.foldable
        if not KERNEL_AVAILABLE or not use_jit or foldable or n == 0:
            out = []
            for lane in range(L):
                lane_ctl = copy.copy(ctl)
                lane_ctl.durs = durs_lanes[lane]
                if nbytes_lanes is not None:
                    lane_ctl.nbytes = np.asarray(nbytes_lanes[lane],
                                                 dtype=np.float64)
                lane_ctl._tpl = None      # template caches embed durations
                out.append(self._np.run_lowered(lane_ctl))
            return out
        ctl.bind(self.idx)
        stat = _static_arrays(ctl, self.idx)
        comp, seqs = _CORE_BATCH(*jax.device_put((*stat, durs_lanes),
                                                 _core_device()))
        comp = np.asarray(comp)
        seqs = np.asarray(seqs, dtype=np.int64)
        out = []
        for lane in range(L):
            nb = None if nbytes_lanes is None else nbytes_lanes[lane]
            out.append(self._postprocess(ctl, comp[lane], seqs[lane],
                                         nbytes=nb))
        return out

    # completion times -> SimResult ---------------------------------------
    def _postprocess(self, ctl: CompiledTaskList, comp: np.ndarray,
                     seqs: np.ndarray,
                     nbytes: Optional[np.ndarray] = None) -> SimResult:
        """Recover the reference bookkeeping from the core's outputs.

        Everything the numpy loop tracks event-by-event is a pure function
        of (completion time, admission seq) per task: deliveries are the
        tasks sorted by the event-heap key ``(time, seq)``; a node's finish
        is the time its coverage countdown (fresh lists) or block bitmap
        (lists with duplicate deliveries) first completes along that order;
        group finishes are per-group maxima."""
        n = ctl.n
        root = self.root
        tb = ctl.total_blocks
        order = np.lexsort((seqs, comp))
        t_ord = comp[order]
        d_ord = np.asarray(ctl.dst, dtype=np.int64)[order]
        nb = (np.asarray(ctl.nbytes, dtype=np.float64)
              if nbytes is None else np.asarray(nbytes, dtype=np.float64))
        deliveries = list(zip(t_ord.tolist(), nb[order].tolist()))

        node_finish = {root: 0.0}
        if ctl.all_fresh:
            # per-node countdown: group the completion order by node and
            # find where the within-node span cumsum first reaches the
            # total block count
            s_ord = np.asarray(ctl.spans, dtype=np.int64)[order]
            by_node = np.lexsort((np.arange(n), d_ord))
            dd = d_ord[by_node]
            cs = np.cumsum(s_ord[by_node])
            starts = np.searchsorted(dd, np.unique(dd))
            base = np.zeros(n, dtype=np.int64)
            base[starts] = np.concatenate(([0], cs[starts[1:] - 1]))
            within = cs - np.maximum.accumulate(base)
            hit = (within >= tb) & (within - s_ord[by_node] < tb)
            for k in np.nonzero(hit)[0]:
                v = int(dd[k])
                if v != root:
                    node_finish[v] = float(t_ord[by_node][k])
        else:
            # bitmap path: a block counts at its earliest delivery, a node
            # finishes when its last missing block lands
            lo = np.asarray([b[0] for b in ctl.blks], dtype=np.int64)[order]
            sp = np.asarray(ctl.spans, dtype=np.int64)[order]
            reps = np.repeat(np.arange(n), sp)
            off = np.arange(reps.size) - np.repeat(
                np.concatenate(([0], np.cumsum(sp)[:-1])), sp)
            blkid = lo[reps] + off
            key = d_ord[reps] * tb + blkid
            tt = t_ord[reps]
            earliest = np.full(ctl.num_nodes * tb, np.inf)
            np.minimum.at(earliest, key, tt)
            per_node = earliest.reshape(ctl.num_nodes, tb)
            covered = np.isfinite(per_node).all(axis=1)
            fins = per_node.max(axis=1)
            for v in range(ctl.num_nodes):
                if v != root and covered[v]:
                    node_finish[v] = float(fins[v])

        missing = [v for v in range(ctl.num_nodes) if v not in node_finish]
        assert not missing, \
            f"nodes {missing[:5]} never got the full message"

        gf: List[float] = []
        if any(g is not None for g in ctl.grps):
            group_last = {}
            for i in order:
                g = ctl.grps[i]
                if g is not None:
                    group_last[g] = float(comp[i])
            gf = [group_last[g] for g in sorted(group_last)]

        return SimResult(finish_time=max(node_finish.values()),
                         node_finish=node_finish, deliveries=deliveries,
                         group_finish=gf, started=n, completed=n)


def lower_baseline_lanes(topo: Topology, cm: ConflictModel, name: str,
                         root: int, sizes: Sequence[float],
                         ) -> Tuple[CompiledTaskList, np.ndarray,
                                    np.ndarray]:
    """Lower baseline ``name`` at each message size and stack the lanes.

    Verifies the lowered structure is size-invariant (true for the
    whole-message tree family and srda, whose task graphs do not depend on
    the payload; the chain family re-segments per size and is rejected) and
    returns ``(ctl, durs [L, n], nbytes [L, n])`` ready for
    ``KernelSim.run_lowered_batch``."""
    from repro.core.baselines import lower_baseline

    ctls = [lower_baseline(topo, cm, name, root, s) for s in sizes]
    ctl0 = ctls[0]
    for c in ctls[1:]:
        same = (c.n == ctl0.n and c.rank == ctl0.rank
                and c.deps == ctl0.deps and c.dst == ctl0.dst
                and c.blks == ctl0.blks and c.res_ids == ctl0.res_ids)
        if not same:
            raise ValueError(
                f"baseline {name!r} does not keep one lowered structure "
                f"across message sizes; sweep it without lane batching")
    durs = np.asarray([c.durs for c in ctls], dtype=np.float64)
    nbytes = np.asarray([c.nbytes for c in ctls], dtype=np.float64)
    return ctl0, durs, nbytes
