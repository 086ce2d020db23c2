"""Compile rehearsal for a TPU v5e 2x2 host, without the chip.

The TPU compiler is installed with jax and compiles for a chip that is
described rather than attached, so these tests catch what interpret mode
and the CPU backend cannot: Mosaic's tiling and VMEM limits, programs that
do not fit a chip's 16 GB of HBM, and collectives the partitioner inserts.
Nothing runs; a passing compile says nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file. Keep these tests in this one file.
"""

import os
import re

import numpy as np
import pytest

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jax build
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _max_plen(rows: int) -> int:
    """Longest row (a multiple of 1024) whose buffer the kernel accepts."""
    from repro.device.pallas_step import VMEM_LIMIT_BYTES, kernel_vmem_bytes
    plen = 1024
    while kernel_vmem_bytes((rows, plen + 1024)) <= VMEM_LIMIT_BYTES:
        plen += 1024
    return plen


@pytest.mark.parametrize("shape", ["ladder_1mib", "vmem_budget_edge",
                                   "tiled_rows", "ladder_1mib_tiled"])
def test_pallas_round_step_compiles_for_v5e(one_chip, shape):
    """The kernel compiles under Mosaic at the torus2d(2,2) 1 MiB plan's
    packet buffer (6 rows of 43691 f32), at the largest buffer
    ``check_kernel_limits`` lets through, and on rows of whole (8, 128)
    tiles, as ``bbs_broadcast`` lays them out: 8 rows of the 250 MiB
    plan's (5024, 128) and the 1 MiB plan's 6 rows of (344, 128)."""
    import jax
    import jax.numpy as jnp
    from repro.device.pallas_step import _round_step_pallas, check_kernel_limits

    buf = {"ladder_1mib": (6, 43691), "vmem_budget_edge": (8, _max_plen(8)),
           "tiled_rows": (8, 5024, 128),
           "ladder_1mib_tiled": (6, 344, 128)}[shape]
    check_kernel_limits(buf, jnp.float32)
    args = (jax.ShapeDtypeStruct(buf, jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct(buf[1:], jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(
        lambda b, r, s: _round_step_pallas(b, r, s, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bbs_broadcast_compiles_for_v5e_2x2(topo):
    """The whole 4-chip program for torus2d(2,2) at 256 MiB: one
    collective-permute per sub-round of the cycle, and it fits a chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import api
    from repro.core import topology as T
    from repro.device import bbs_broadcast

    nbytes = 256 * MIB
    ex = api.compile(T.torus2d(2, 2)).executable(0, nbytes)
    mesh = Mesh(np.array(topo.devices), ("dev",))
    x = jax.ShapeDtypeStruct((nbytes // 4,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = jax.jit(lambda v: bbs_broadcast(
        v, mesh, "dev", ex.schedule, ex.num_groups)).lower(x).compile()
    txt = compiled.as_text()
    permutes = re.findall(r"collective-permute(?:-start)?\(", txt)
    assert len(permutes) == ex.schedule.d
    mem = compiled.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < 16e9


def _instructions(hlo: str):
    """(name, opcode, op_name) of each instruction of an HLO module's text;
    the entry computation's root is named first."""
    out, root = [], None
    for line in hlo.splitlines():
        m = re.match(r"^\s*(ROOT\s+)?%(\S+) = .*?\s([a-z][\w-]*)\(", line)
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        ins = (m.group(2), m.group(3), name.group(1) if name else "")
        out.append(ins)
        if m.group(1):
            root = ins                  # the entry computation prints last
    return [root] + out


def _entry(hlo: str) -> str:
    """The text of an HLO module's entry computation."""
    start = hlo.index("\nENTRY ")
    return hlo[start:hlo.index("\n}", start)]


def _phase(op_name: str) -> str:
    inner = [p for p in op_name.split("/") if p.startswith("bcast.")]
    return inner[-1] if inner else ""


def test_bbs_broadcast_phases_are_named_for_v5e_2x2(topo):
    """The benchmark's broadcast (250 MiB f32 from root 0 on torus2d(2, 2))
    compiled for a v5e 2x2 keeps its phase scopes in the ``op_name`` of
    the instructions a trace names: every collective permute under
    ``bcast.cycle`` and in no inner phase, every row write of the cycle
    under ``bcast.step``, the pad under ``bcast.place`` and the final
    slice under ``bcast.unstack``. Its packet rows are whole (8, 128)
    tiles, so the flat payload goes into and out of the buffer by bitcasts:
    the entry holds the cycle's loop and no relayout loop, and the
    temporaries come to about one payload. With one fabric node per chip
    the fold hands nothing over inside a chip: no op under
    ``bcast.local``, and 104 collective permutes a broadcast (one per
    sub-round, ``d`` in the cycle's body)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import api
    from repro.core import topology as T
    from repro.device import fold_schedule

    nbytes = 250 * MIB
    ex = api.compile(T.torus2d(2, 2, preset="tpu_ici")).executable(0, nbytes)
    mesh = Mesh(np.array(topo.devices), ("dev",))
    x = jax.ShapeDtypeStruct((nbytes // 4,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = ex.lower(x, mesh).compile()
    hlo = compiled.as_text()
    root, *ins = _instructions(hlo)
    permutes = [i for i in ins if i[1].startswith("collective-permute")]
    assert len(permutes) == 2 * ex.schedule.d          # -start and -done
    assert {_phase(i[2]) for i in permutes} == {"bcast.cycle"}
    writes = [i for i in ins if i[1] == "dynamic-update-slice"
              and "bcast.cycle" in i[2]]
    assert len(writes) == ex.schedule.d + 1           # one per round step
    assert {_phase(i[2]) for i in writes} == {"bcast.step"}
    assert not [i for i in ins if _phase(i[2]) == "bcast.local"]
    assert fold_schedule(ex.schedule, 4).counters(
        ex.num_groups)["permutes"] == 104
    pads = [i for i in ins if i[1] == "pad"]
    assert pads and {_phase(i[2]) for i in pads} == {"bcast.place"}
    assert root[1] == "slice" and _phase(root[2]) == "bcast.unstack"
    assert len(re.findall(r" while\(", _entry(hlo))) == 1
    rows = ex.num_groups * ex.schedule.K + ex.schedule.num_relay
    assert rows == 102 and "f32[102,5024,128]" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.1 * nbytes


# each chip's buffer: its nodes' packet rows and a sink row each, end to
# end (128 x (334 + 1) rows of (96, 128) f32; 32 x (668 + 1) of (192, 128))
FOLDED = {"one_chip_16m": (1, 16_000_000, "f32[42880,96,128]"),
          "four_chips_64m": (4, 64_000_000, "f32[21408,192,128]")}


@pytest.mark.parametrize("cell", sorted(FOLDED))
def test_folded_dragonfly_compiles_for_v5e(topo, cell):
    """dragonfly(128) folded onto one chip at 16e6 B and onto the 2x2 at
    64e6 B, as the benchmark runs it: each chip holds its nodes' packet
    buffers end to end, hands rows over inside the chip under
    ``bcast.local``, runs one collective permute per chip-level
    permutation of the cycle (none on one chip), and fits a chip with the
    buffer updated in place (temporaries: the buffer and the unstacking's
    relayout, each about the output)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import api
    from repro.core import topology as T
    from repro.device import fold_schedule

    chips, nbytes, buf = FOLDED[cell]
    ex = api.compile(T.by_name("dragonfly", 128),
                     "full_duplex").executable(0, nbytes)
    mesh = Mesh(np.array(topo.devices[:chips]), ("dev",))
    x = jax.ShapeDtypeStruct((nbytes // 4,), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = ex.lower(x, mesh).compile()
    hlo = compiled.as_text()
    _, *ins = _instructions(hlo)
    starts = [i for i in ins if i[1] == "collective-permute-start"]
    fold = fold_schedule(ex.schedule, chips)
    assert len(starts) == sum(len(r.permutes) for r in fold.rounds)
    assert (len(starts) == 0) == (chips == 1)
    assert any(_phase(i[2]) == "bcast.local" for i in ins)
    assert buf in hlo
    mem = compiled.memory_analysis()
    out = mem.output_size_in_bytes
    assert out == 128 * nbytes // chips
    assert mem.temp_size_in_bytes < 3.2 * out
    assert mem.argument_size_in_bytes + out + mem.temp_size_in_bytes < 16e9


def test_kernelsim_core_compiles_for_v5e(one_chip):
    """The float64 event core compiles for the TPU; it runs on the host
    CPU for exactness, not because XLA:TPU refuses it."""
    import jax
    from repro.core import kernelsim as KS
    from repro.core import topology as T
    from repro.core.baselines import lower_baseline
    from repro.core.intersection import FULL_DUPLEX, ConflictModel

    topo16 = T.mesh2d(16, 16)
    cm = ConflictModel(topo16, FULL_DUPLEX)
    ks = KS.KernelSim(topo16, cm, 0)
    ctl = lower_baseline(topo16, cm, "binomial", 0, 64e6)
    ctl.bind(ks.idx)
    arrays = (*KS._static_arrays(ctl, ks.idx),
              np.asarray(ctl.durs, dtype=np.float64))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in arrays]
    compiled = KS._CORE.lower(*args).compile()
    assert "while" in compiled.as_text()
