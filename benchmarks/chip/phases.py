"""The broadcast program's phases in a traced run: where its device time
goes, when each run starts after it is asked for, and how far each chip's
clock can be off the host's.

The program names its phases with ``jax.named_scope``
(``repro.device.runner``): ``bcast.place``, ``bcast.cycle``, ``bcast.step``,
``bcast.unstack``. A scope is ``op_name`` metadata of the compiled program.
The trace reduction (``trace_reduce.Trace``) keeps an operation by its
instruction's name only, so the scopes are read from the HLO text of the
program the run dispatched (``ExecutablePlan.lower``), keyed by module and
instruction name: an operation belongs to the
module whose ``XLA Modules`` event holds it, and that event is named
``<module>(<id>)``. Instruction names are the same whatever the metadata,
so a trace recorded before the scopes existed reads the same way.
"""

from __future__ import annotations

import bisect
import functools
import heapq
import re
from typing import Dict, List, Optional, Tuple

from trace_reduce import BENCH_MODULE, merge

SCOPE = "bcast."
PLACE, STEP, UNSTACK = "bcast.place", "bcast.step", "bcast.unstack"
PERMUTE, OTHER = "permute", "other"
LAUNCH_SPAN = "bench.request"   # encloses the call into ``ExecutablePlan.run``
RECORDED_TOPOLOGY = "v5e:2x2"   # where the traces of ``tests/data`` were taken

_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+)\s*=(.*)$')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def module_base(event_name: str) -> str:
    """``jit_run`` out of a module event's name, ``jit_run(2318...)``."""
    return event_name.split("(", 1)[0]


def hlo_scopes(text: str) -> Tuple[str, Dict[str, str]]:
    """The module's name and ``{instruction: op_name}`` of every instruction
    in an HLO module's text (``""`` where it has no ``op_name``)."""
    head = re.search(r"^HloModule\s+([^\s,]+)", text, re.M)
    if head is None:
        raise ValueError("no HloModule line in the HLO text")
    scopes = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(m.group(2))
            scopes[m.group(1)] = name.group(1) if name else ""
    return head.group(1), scopes


def phase_of(op_name: str) -> str:
    """The innermost ``bcast.*`` scope of an ``op_name`` path, or
    ``other`` where it has none (work the compiler put in)."""
    inner = [p for p in op_name.split("/") if p.startswith(SCOPE)]
    return inner[-1] if inner else OTHER


def plan_of(cell):
    """The cell's ``ExecutablePlan``, built as the broadcast driver builds
    it."""
    import common

    root = int(cell.traffic["root"])
    model, _ = common.build_model(cell.config, root)
    return model.executable(root, int(cell.traffic["message_bytes"]),
                            algo=cell.config["algo"])


def describe_program(ex, x, mesh) -> dict:
    """The program that ``ex.run(x, mesh)`` dispatches: module name,
    ``{instruction: op_name}`` of its optimized HLO, and the schedule's
    ``perms`` (the ``(source, target)`` pairs of each sub-round's permute).
    A program older than its phase scopes has no ``lower``; it is described
    by its ``perms`` alone."""
    program = {"module": None, "scopes": {},
               "perms": [[list(p) for p in perm]
                         for perm in ex.schedule.perms]}
    if hasattr(ex, "lower"):
        program["module"], program["scopes"] = hlo_scopes(
            ex.lower(x, mesh).compile().as_text())
    return program


class Phases:
    """The phase readings of one trace of the broadcast program
    (``describe_program``'s description of it)."""

    def __init__(self, trace, program: Optional[dict] = None):
        """``program`` is ``describe_program``'s; without it the readings
        that need no scope (``prelaunch_ns``; ``clock_offsets`` and
        ``prelaunch_ms`` from the host spans alone) are still made."""
        program = program or {"module": None, "scopes": {}, "perms": []}
        self.trace = trace
        self.module = program["module"]
        self.scopes = program["scopes"]
        self.perms = [[tuple(p) for p in perm] for perm in program["perms"]]
        self._split: Dict[int, Dict[str, float]] = {}

    # -- the program's runs ------------------------------------------------

    def _runs(self, chip: int) -> List[Tuple[str, float, float, list]]:
        """(module, start, end, ops) of each run of a program of the
        system under test on ``chip``, its ops as (name, start, duration),
        in start order."""
        dev = self.trace.devices[chip]
        mods = sorted((s, s + d, n) for n, s, d in dev["modules"]
                      if BENCH_MODULE not in n)
        starts = [m[0] for m in mods]
        runs = [(n, s, e, []) for s, e, n in mods]
        for ev in dev["ops"]:
            k = bisect.bisect_right(starts, ev[1]) - 1
            if k >= 0 and ev[1] < mods[k][1]:
                runs[k][3].append(ev)
        for r in runs:
            r[3].sort(key=lambda e: e[1])
        return runs

    @functools.cached_property
    def scoped(self) -> bool:
        """Whether the program carries the phase scopes (one built before
        them has none) and ran in the trace. Raises ``ValueError`` where an
        operation of one of its runs is no instruction of it: the trace
        then shows another program than the one described."""
        if not any(phase_of(v) != OTHER for v in self.scopes.values()):
            return False
        ran = False
        for chip in range(self.trace.chips):
            for mod, _, _, ops in self._runs(chip):
                if module_base(mod) != self.module:
                    continue
                ran = True
                unknown = sorted({n for n, _, _ in ops
                                  if n not in self.scopes})
                if unknown:
                    raise ValueError(
                        f"{len(unknown)} operations of {mod} on chip {chip} "
                        f"are not instructions of the described program "
                        f"{self.module}: {unknown[:5]}")
        return ran

    # -- where the device time goes ----------------------------------------

    def split_ns(self, chip: int) -> Dict[str, float]:
        """The chip's program busy time inside the window, partitioned:
        each instant goes to ``permute`` while a collective permute is in
        flight (``Trace.permutes``); else to the phase of the innermost
        (shortest) operation then running, ``other`` where it has no scope.
        """
        if chip not in self._split:
            self._split[chip] = self._partition(chip)
        return self._split[chip]

    def _partition(self, chip: int) -> Dict[str, float]:
        tr = self.trace
        lo, hi = 0.0, tr.window_ns
        ops = []
        for mod, _, _, evs in self._runs(chip):
            own = module_base(mod) == self.module
            for n, s, d in evs:
                a, b = max(s, lo), min(s + d, hi)
                if b > a:
                    ph = phase_of(self.scopes.get(n, "")) if own else OTHER
                    ops.append((a, b, d, ph))
        edges = []
        for i, (a, b, _, _) in enumerate(ops):
            edges += [(a, 1, i), (b, -1, i)]
        for a, b in merge(tr.permutes(chip)):
            edges += [(a, 1, -1), (b, -1, -1)]
        edges.sort()
        out: Dict[str, float] = {}
        active: List[Tuple[float, int]] = []     # (duration, op) heap
        ended = set()
        in_flight = 0
        prev = None
        for t, kind, i in edges:
            if prev is not None and t > prev:
                while active and active[0][1] in ended:
                    heapq.heappop(active)
                if in_flight:
                    ph = PERMUTE
                elif active:
                    ph = ops[active[0][1]][3]
                else:
                    ph = None
                if ph is not None:
                    out[ph] = out.get(ph, 0.0) + t - prev
            prev = t
            if i < 0:
                in_flight += kind
            elif kind > 0:
                heapq.heappush(active, (ops[i][2], i))
            else:
                ended.add(i)
        return out

    def phase_ms(self, phase: str) -> Optional[float]:
        """Device time per broadcast under ``phase`` (``bcast.place``,
        ``bcast.step``, ``bcast.unstack``, or ``other`` for every local
        instant under none of those), mean over the chips, in ms."""
        tr = self.trace
        n = tr.request_count()
        if not tr.chips or not n or not self.scoped:
            return None
        named = (PLACE, STEP, UNSTACK)
        total = 0.0
        for c in range(tr.chips):
            split = self.split_ns(c)
            if phase == OTHER:
                total += sum(v for k, v in split.items()
                             if k != PERMUTE and k not in named)
            else:
                total += split.get(phase, 0.0)
        return total / tr.chips / n * 1e-6

    # -- before the program starts -----------------------------------------

    def prelaunch_ns(self, chip: int) -> List[float]:
        """For each launch inside the window (the start of a
        ``bench.request`` span), the time to the first operation of the
        program run it launched on ``chip``: the first run that starts
        after it and before the next launch."""
        tr = self.trace
        launches = sorted(s for n, s, _ in tr.host if n == LAUNCH_SPAN)
        runs = [(s, ops) for _, s, _, ops in self._runs(chip) if ops]
        starts = [s for s, _ in runs]
        out = []
        for i, t in enumerate(launches):
            nxt = launches[i + 1] if i + 1 < len(launches) else tr.window_ns
            k = bisect.bisect_left(starts, t)
            if t < 0 or k == len(runs) or starts[k] >= nxt:
                continue
            first = runs[k][1][0][1]
            if first <= tr.window_ns:
                out.append(first - t)
        return out

    def prelaunch_ms(self) -> Optional[float]:
        """Mean over the chips of the mean ``prelaunch_ns``, in ms. The gap
        starts on the host's clock and ends on the chip's, so each chip's
        is corrected by the midpoint of its ``clock_offsets`` interval; a
        chip whose interval is open on a side is left out."""
        offsets = self.clock_offsets()
        per_chip = []
        for c in range(self.trace.chips):
            gaps, (lo, hi) = self.prelaunch_ns(c), offsets[c]
            if gaps and lo is not None and hi is not None:
                per_chip.append(sum(gaps) / len(gaps) * 1e-6 - (lo + hi) / 2)
        if not per_chip:
            return None
        return sum(per_chip) / len(per_chip)

    # -- how far each chip's clock may be off the host's ------------------

    def clock_offsets(self) -> List[List[Optional[float]]]:
        """``[lo, hi]`` in ms for each chip: the bounds that causality puts
        on (the chip's clock as the trace reads it) - (the host's).

        A program cannot start before the host span that launches it starts
        (``bench.payload`` for the benchmark's payload program, the request
        for the broadcast), nor end after the host span that waits for it
        ends (``bench.payload``, ``bench.wait``). Across chips, the ``j``-th
        collective permute of a run cannot be done on a target before it
        started on the source that sends to it (sub-round ``j mod d`` of
        the schedule's ``perms``; node ``i`` runs on chip ``i``), which
        carries each chip's bounds to its neighbours. ``None`` where a side
        stays unbounded."""
        tr = self.trace
        inf = float("inf")
        lo, hi = [-inf] * tr.chips, [inf] * tr.chips
        spans = {n: sorted((s, s + d) for m, s, d in tr.host if m == n)
                 for n in ("bench.payload", LAUNCH_SPAN, "bench.wait")}

        def overlapping(kind, a, b):
            best, got = 0.0, None
            for s, e in spans[kind]:
                ov = min(b, e) - max(a, s)
                if ov > best:
                    best, got = ov, (s, e)
            return got

        by_request: Dict[float, Dict[int, list]] = {}
        for c in range(tr.chips):
            for (mod_s, mod_e, mod) in ((s, s + d, n) for n, s, d
                                        in tr.devices[c]["modules"]):
                bench = BENCH_MODULE in mod
                wait = overlapping("bench.payload" if bench else "bench.wait",
                                   mod_s, mod_e)
                if wait is None:
                    continue
                lo[c] = max(lo[c], mod_e - wait[1])
                if bench:
                    hi[c] = min(hi[c], mod_s - wait[0])
                    continue
                launch = [s for s, _ in spans[LAUNCH_SPAN] if s <= wait[0]]
                if launch:
                    hi[c] = min(hi[c], mod_s - launch[-1])
            permutes = tr.permutes(c)
            for _, s, e, _ in self._runs(c):
                wait = overlapping("bench.wait", s, e)
                if wait is not None:
                    by_request.setdefault(wait[0], {})[c] = [
                        p for p in permutes if s <= p[0] and p[1] <= e]
        d = len(self.perms)
        gap: Dict[Tuple[int, int], float] = {}
        for chips in by_request.values():
            counts = {len(v) for v in chips.values()}
            if len(chips) != tr.chips or len(counts) != 1 or not d:
                continue
            for j in range(counts.pop()):
                for src, dst in self.perms[j % d]:
                    if src < tr.chips and dst < tr.chips:
                        w = chips[dst][j][1] - chips[src][j][0]
                        gap[src, dst] = min(gap.get((src, dst), inf), w)
        for _ in range(tr.chips):
            for (src, dst), w in gap.items():
                hi[dst] = min(hi[dst], hi[src] + w)
                lo[src] = max(lo[src], lo[dst] - w)
        ms = lambda v: None if abs(v) == inf else v * 1e-6  # noqa: E731
        return [[ms(a), ms(b)] for a, b in zip(lo, hi)]


def _program_of(cell) -> dict:
    """``describe_program`` of the cell's broadcast. On a TPU: the plan the
    driver builds, called with a payload made as the driver makes it (same
    program, same placement), its compile loaded from the compile cache
    the run filled. Without a TPU the only traces to read are those
    recorded on a v5e 2x2 (``tests/data``; ``tests/test_trace.py`` reads
    every reader on them), so the program is compiled for a described
    ``RECORDED_TOPOLOGY``, its payload replicated as ``shard_map`` takes
    it, which gives the instruction names the chip ran."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import common

    ex = plan_of(cell)
    dtype = jnp.dtype(cell.config["dtype"])
    words = int(cell.traffic["message_bytes"]) // dtype.itemsize
    if jax.devices()[0].platform == "tpu":
        x = common.payload_fn(words, dtype)(common.base_key(0), 0)
        return describe_program(ex, x, ex.mesh())
    from jax.experimental import topologies
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=RECORDED_TOPOLOGY).devices
    mesh = Mesh(np.array(devices[:cell.chips]), (ex.device.axis,))
    x = jax.ShapeDtypeStruct((words,), dtype,
                             sharding=NamedSharding(mesh, P()))
    return describe_program(ex, x, mesh)


def of(run) -> Optional[Phases]:
    """The ``Phases`` of a traced run, made once per run; ``None`` without
    a trace."""
    if run.trace is None:
        return None
    ph = run.__dict__.get("_phases")
    if ph is None:
        ph = run._phases = Phases(run.trace, _program_of(run.cell))
    return ph
