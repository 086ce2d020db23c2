"""Reduction of a profiler trace to the numbers the per-layer readers need.

``Trace.from_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote,
keeps the device operations and modules of the cell's chips and the
benchmark's own host spans (``bench.*``), and puts every time on one clock,
in nanoseconds from the start of the ``bench.window`` span. ``Trace.load``
reads the same content back from the compact ``.json.gz`` that ``trim``
writes (the tests' recorded trace).

On a TPU the chips are the planes ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation and ``XLA Modules`` one per program
execution. An operation's event is named by its whole HLO text
(``%fusion.9 = u32[642510]... fusion(...)``); it is kept as the
instruction's name (``fusion.9``). A ``while`` loop is an event of its own
that spans the operations of its body. Modules of the benchmark's own programs (payload, check) carry
``bench_`` in their names; everything else on the device is the system
under test.
"""

from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
BENCH_MODULE = "bench_"
COLLECTIVE_PERMUTE = "collective-permute"
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.payload", "bench.request", "bench.wait")
CONTAINERS = re.compile(r"^(while|conditional|call)[.\d]*$")


def op_name(name: str) -> str:
    """The HLO instruction's name out of an op event's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of ``(start, end)`` intervals, as disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


class Trace:
    def __init__(self, devices: List[Dict[str, List[Event]]],
                 host: List[Event], window_ns: float):
        self.devices = devices        # per chip: {"ops": [...], "modules": [...]}
        self.host = host              # bench.* spans
        self.window_ns = window_ns    # [0, window_ns] is the measured window
        self.lines = []               # what from_dir found, for diagnosis

    # -- reading ---------------------------------------------------------------

    @classmethod
    def from_dir(cls, tdir: str, chips: int) -> "Trace":
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {tdir}")
        pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
        devices: Dict[int, Dict[str, List[Event]]] = {}
        host: List[Event] = []
        lines_seen = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                        line.name)
                    evs = [(op_name(e.name), e.start_ns, e.duration_ns)
                           for e in line.events]
                    if key:             # a plane may hold several such lines
                        lines[key] += evs
                    if evs:
                        lines_seen.append((plane.name, line.name, len(evs),
                                           evs[0][1], evs[-1][1]))
                devices[int(m.group(1))] = lines
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    host += [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench.")]
        win = [e for e in host if e[0] == WINDOW_SPAN]
        if not win:
            raise ValueError("the trace has no bench.window span")
        t0, dur = win[0][1], win[0][2]
        shift = lambda evs: [(n, s - t0, d) for n, s, d in evs]
        chips_seen = sorted(devices)[:chips]
        tr = cls([{k: shift(v) for k, v in devices[c].items()}
                  for c in chips_seen],
                 shift([e for e in host if e[0] != WINDOW_SPAN]), dur)
        tr.window_ns = tr.covered_ns()
        # (plane, line, events, first start, last start) in window time
        tr.lines = [(p, ln, n, a - t0, b - t0)
                    for p, ln, n, a, b in lines_seen]
        return tr

    def covered_ns(self) -> float:
        """The end of the part of the window that every chip's trace
        covers, cut back to the end of the last request wholly inside it.

        The profiler can stop recording one chip early: on a v5e 2x2 the
        root chip's ops ended 1.7 s into a 20 s window while the other
        chips' went on. Reading past that end would count the rest as
        idle on that chip."""
        ends = [max((s + d for _, s, d in dev["ops"]), default=0.0)
                for dev in self.devices]
        limit = min([self.window_ns] + ends)
        waits = [s + d for n, s, d in self.host
                 if n == "bench.wait" and s + d <= limit]
        return max(waits) if waits else self.window_ns

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls([{k: [tuple(e) for e in v] for k, v in dev.items()}
                    for dev in d["devices"]],
                   [tuple(e) for e in d["host"]], d["window_ns"])

    def trim(self, path: str, requests: int = 3) -> None:
        """Write the part of the trace from the second request's payload to
        the end of the ``1 + requests``-th request's wait as compact JSON."""
        pay = sorted(e[1] for e in self.host if e[0] == "bench.payload")
        waits = sorted(e[1] + e[2] for e in self.host if e[0] == "bench.wait")
        lo = pay[1] if len(pay) > 1 else 0.0
        hi = waits[min(requests, len(waits) - 1)] if waits else self.window_ns
        keep = lambda evs: [[n, s - lo, d] for n, s, d in evs
                            if s >= lo and s + d <= hi]
        d = {"window_ns": hi - lo,
             "devices": [{k: keep(v) for k, v in dev.items()}
                         for dev in self.devices],
             "host": keep(self.host)}
        with gzip.open(path, "wt") as f:
            json.dump(d, f)

    # -- reductions ------------------------------------------------------------

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9

    @property
    def chips(self) -> int:
        return len(self.devices)

    def request_count(self) -> int:
        """Requests whose ``bench.wait`` span ends inside the window."""
        return sum(1 for n, s, d in self.host
                   if n == "bench.wait" and 0 <= s + d <= self.window_ns)

    def requests(self) -> List[Tuple[float, float]]:
        """(start, end) seconds of each request in the trace: from its
        ``bench.request`` span's start to its ``bench.wait`` span's end."""
        starts = sorted(s for n, s, _ in self.host if n == "bench.request")
        ends = sorted(s + d for n, s, d in self.host if n == "bench.wait")
        return [(a * 1e-9, b * 1e-9) for a, b in zip(starts, ends)]

    def _in_window(self, evs) -> List[Tuple[float, float]]:
        return clip([(s, s + d) for _, s, d in evs], 0.0, self.window_ns)

    def busy_ns(self, chip: int) -> float:
        return total(merge(self._in_window(self.devices[chip]["ops"])))

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, mean over the chips."""
        if not self.chips:
            return None
        return sum(self.busy_ns(c) for c in range(self.chips)) \
            / self.chips * 1e-9

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s()
        if busy is None or self.window_ns <= 0:
            return None
        return 1.0 - busy / self.window_s

    def program_ops(self, chip: int) -> List[Event]:
        """The chip's operations that ran inside a module of the system
        under test (not one of the benchmark's own ``bench_`` programs)."""
        mods = sorted((s, s + d) for n, s, d in self.devices[chip]["modules"]
                      if BENCH_MODULE not in n)
        starts = [m[0] for m in mods]
        out = []
        for ev in self.devices[chip]["ops"]:
            k = bisect.bisect_right(starts, ev[1]) - 1
            if k >= 0 and ev[1] < mods[k][1]:
                out.append(ev)
        return out

    def program_busy_ns(self, chip: int) -> float:
        return total(merge(self._in_window(self.program_ops(chip))))

    def collective_permutes(self, chip: int) -> List[Event]:
        return [e for e in self.program_ops(chip)
                if e[0].startswith(COLLECTIVE_PERMUTE)]

    def permutes(self, chip: int) -> List[Tuple[float, float]]:
        """(start, end) of each collective permute that ran wholly inside
        the window: from its ``-start`` half's start to its ``-done`` half's
        end, since the row is in flight all that time whatever else runs in
        between; the ``-done`` half alone where no ``-start`` was recorded,
        and the op itself where it is not split."""
        opened: Dict[str, float] = {}
        out = []
        for n, s, d in sorted(self.collective_permutes(chip),
                              key=lambda e: e[1]):
            if "-start" in n:
                opened[n.replace("-start", "", 1)] = s
                continue
            begin = opened.pop(n.replace("-done", "", 1), s)
            if begin >= 0 and s + d <= self.window_ns:
                out.append((begin, s + d))
        return out

    def permute_ns(self, chip: int) -> float:
        """Device time in which a collective permute was in flight."""
        return total(merge(self.permutes(chip)))

    def permute_count(self, chip: int) -> int:
        """Collective permutes run inside the window: the same ones that
        ``permute_ns`` times."""
        return len(self.permutes(chip))

    def mean_over_chips(self, fn) -> Optional[float]:
        vals = [fn(c) for c in range(self.chips)]
        vals = [v for v in vals if v is not None]
        return sum(vals) / len(vals) if vals else None

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds, mean over the
        chips; loops that only span their body are left out) and the
        longest idle gaps, each named by the benchmark's host span that
        covers most of it (``host:none`` where none does)."""
        per_op: Dict[str, float] = collections.defaultdict(float)
        gaps = []
        for c in range(self.chips):
            ops = [e for e in self.devices[c]["ops"]
                   if e[1] >= 0 and e[1] + e[2] <= self.window_ns]
            for n, s, d in ops:
                if not CONTAINERS.match(n):
                    per_op[n] += d * 1e-9 / self.chips
            busy = merge(self._in_window(self.devices[c]["ops"]))
            edges = [0.0] + [x for iv in busy for x in iv] + [self.window_ns]
            gaps += [(hi - lo, lo, hi, c)
                     for lo, hi in zip(edges[::2], edges[1::2]) if hi > lo]
        host = [(n, s, s + d) for n, s, d in self.host if n in HOST_SPANS]
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(gaps, reverse=True)[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[self._gap_name(host, lo, hi, c), g * 1e-9]
                              for g, lo, hi, c in gaps]}

    @staticmethod
    def _gap_name(host, lo: float, hi: float, chip: int) -> str:
        best, name = 0.0, "host:none"
        for n, s, e in host:
            ov = min(e, hi) - max(s, lo)
            if ov > best:
                best, name = ov, n
        return f"{name} (chip {chip})"
