"""The folded broadcast (a fabric with more nodes than chips) as its
driver and its per-layer readers need it: the model of a fabric named as
``benchmarks/run.py`` names it, the plan, and the program that ran,
described on the cell's own chips.

``phases.of`` describes the program on ``ExecutablePlan.mesh()`` with no
arguments, which on a host with more chips than the cell uses is not the
cell's mesh; ``phases_of`` describes it on ``jax.devices()[:chips]``, the
devices the driver runs on. Without a TPU the description is compiled for
the chips of a described v5e 2x2, so that a trace recorded there reads
against the instruction names the chip ran; a recorded trace stores its
own description beside it (``tests/data``), which a test hands in by
``use_program``.
"""

from __future__ import annotations

import time
from typing import Iterable, Optional

import phases
from trace_reduce import merge, total

LOCAL = "bcast.local"
RECORDED_TOPOLOGY = phases.RECORDED_TOPOLOGY


def build_model(config: dict, root: int):
    """``repro.api.compile`` of the configuration's fabric, built by
    ``repro.core.topology.by_name`` as ``benchmarks/run.py`` builds the
    paper's fabrics, with an in-memory ``PlanServer``; the plan for
    ``root`` fetched once. Returns the model and ``{"plan_s": seconds}``."""
    from repro import api, device  # noqa: F401  (imported before timing)
    from repro.core import topology as T

    fab = config["fabric"]
    topo = T.by_name(fab["by_name"], int(fab["n"]))
    model = api.compile(topo, fab["mode"], server=True)
    t0 = time.perf_counter()
    model.plan(root)
    return model, {"plan_s": time.perf_counter() - t0}


def plan_of(cell):
    """The cell's ``ExecutablePlan``, built as the folded driver builds
    it."""
    root = int(cell.traffic["root"])
    model, _ = build_model(cell.config, root)
    return model.executable(root, int(cell.traffic["message_bytes"]),
                            algo=cell.config["algo"])


def row_bytes(words: int, rows: int, itemsize: int) -> int:
    """Bytes of one packet row as the runner lays it out: the payload cut
    into ``rows`` rows, each rounded up to whole 4 KiB tiles
    (``repro.device.runner._pad_packets``)."""
    tile = 4096 // itemsize
    return -(-words // (rows * tile)) * tile * itemsize


def describe(cell) -> dict:
    """``phases.describe_program`` of the cell's broadcast on the cell's
    own chips: on a TPU the first ``chips`` devices, with a payload made
    as the driver makes it (the compile comes from the cache the run
    filled); without one the first ``chips`` of a described v5e 2x2."""
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
        return phases.describe_program(
            ex, x, ex.mesh(jax.devices()[:cell.chips]))
    from jax.experimental import topologies
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=RECORDED_TOPOLOGY).devices
    mesh = Mesh(np.array(devices[:cell.chips]), (ex.device.axis,))
    x = jax.ShapeDtypeStruct((words,), dtype,
                             sharding=NamedSharding(mesh, P()))
    return phases.describe_program(ex, x, mesh)


def use_program(run, program: dict) -> None:
    """Read ``run`` against a stored description of its program."""
    run._fold_phases = phases.Phases(run.trace, program)


def phases_of(run) -> Optional[phases.Phases]:
    """The ``Phases`` of a traced run of the folded broadcast, its program
    described on the cell's chips, made once per run; ``None`` without a
    trace."""
    if run.trace is None:
        return None
    ph = run.__dict__.get("_fold_phases")
    if ph is None:
        use_program(run, describe(run.cell))
        ph = run._fold_phases
    return ph


def scoped_busy_ns(ph: phases.Phases, chip: int, scopes: Iterable[str]
                   ) -> float:
    """Device time on ``chip`` inside the window in which an operation of
    the described program whose innermost scope is one of ``scopes`` runs,
    whether or not a collective permute is in flight meanwhile."""
    scopes = set(scopes)
    hi = ph.trace.window_ns
    spans = []
    for mod, _, _, ops in ph._runs(chip):
        if phases.module_base(mod) != ph.module:
            continue
        for n, s, d in ops:
            if phases.phase_of(ph.scopes.get(n, "")) in scopes:
                a, b = max(s, 0.0), min(s + d, hi)
                if b > a:
                    spans.append((a, b))
    return total(merge(spans))


def main(argv=None) -> int:
    """Write ``describe`` of a cell's program on this machine's chips as
    JSON, to store beside a trace of that cell recorded here:

        python3 benchmarks/chip/folded.py <workload> <path.program.json>
    """
    import json
    import pathlib
    import sys

    import bench

    args = sys.argv[1:] if argv is None else argv
    if len(args) != 2:
        print(main.__doc__, file=sys.stderr)
        return 2
    bench.env_for_run()
    bench.enable_cache()
    pathlib.Path(args[1]).write_text(json.dumps(describe(
        bench.load_cell(args[0]))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
