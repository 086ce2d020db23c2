"""Device-execution tests: schedules, runners, calibration, the executable
API and the collectives deprecation shim.

Schedule compilation, symmetry round-trips and the calibration artifact
plumbing run in-process (single CPU device). Anything that actually runs a
broadcast on a mesh spawns a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main pytest
process must keep a single device — same discipline as
tests/test_collectives.py).
"""

import json
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidevice(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"        # emulated devices, never a chip
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


# ---------------------------------------------------------------------------
# Schedule compilation (in-process)
# ---------------------------------------------------------------------------

def _schedules_equal_under(perm, s0, s1):
    """s1 must be s0 with the device axis relabeled by ``perm``."""
    assert (s1.K, s1.d, s1.max_arrival, s1.num_relay) == \
        (s0.K, s0.d, s0.max_arrival, s0.num_relay)
    for r in range(s0.d):
        assert {(perm[a], perm[b]) for a, b in s0.perms[r]} == \
            set(s1.perms[r]), f"round {r} matching differs"
    for t0, t1 in ((s0.send_rel, s1.send_rel), (s0.recv_rel, s1.recv_rel),
                   (s0.send_abs, s1.send_abs), (s0.recv_abs, s1.recv_abs)):
        for r in range(s0.d):
            for v in range(s0.num_devices):
                assert t0[r][v] == t1[r][perm[v]], \
                    f"table mismatch at round {r}, device {v}"


@pytest.mark.parametrize("mk", ["ring", "hypercube", "mesh2d"])
def test_schedule_symmetry_roundtrip(mk):
    """Relabeled plan -> device schedule == permuted representative
    schedule, for every candidate — including candidates with pinned route
    overrides and relay chains (the PR 7 orbit-sharing contract extended to
    the device tables)."""
    from repro.core import topology as T
    from repro.core.bbs import build_plan
    from repro.core.intersection import ConflictModel
    from repro.core.symmetry import relabel_plan
    from repro.device import NotDeviceExecutable, make_device_schedule

    topo = {"ring": lambda: T.ring(8),
            "hypercube": lambda: T.hypercube(3),
            "mesh2d": lambda: T.mesh2d(3, 3)}[mk]()
    n = topo.num_nodes
    orbits = topo.automorphisms().orbits()
    rep, w = orbits.rep_of[n - 1], orbits.witness(n - 1)
    assert w[rep] == n - 1
    plan = build_plan(topo, root=rep)
    rplan = relabel_plan(plan, w)
    compiled = ConflictModel(topo).compiled()
    seen_override = seen_relay = False
    for c, rc in zip(plan.candidates, rplan.candidates):
        try:
            s0 = make_device_schedule(c.pipeline, n, compiled=compiled)
        except NotDeviceExecutable:
            with pytest.raises(NotDeviceExecutable):
                make_device_schedule(rc.pipeline, n, compiled=compiled)
            continue
        s1 = make_device_schedule(rc.pipeline, n, compiled=compiled)
        _schedules_equal_under(w, s0, s1)
        seen_override |= rc.pipeline.routes is not None
        seen_relay |= s0.num_relay > 0
    # the round-trip must have exercised the interesting machinery, not
    # just identity tables
    assert seen_relay, "no candidate produced relay chains"
    if mk in ("ring", "mesh2d"):
        assert seen_override, "no relabeled candidate carried route overrides"


def test_baseline_trees_compile_to_schedules():
    """Whole-message baseline trees lower through build_pipeline into
    device schedules; multi-hop strides become relay chains."""
    from repro.core import topology as T
    from repro.core.intersection import ConflictModel
    from repro.device import build_executable

    topo = T.ring(8)
    cm = ConflictModel(topo)
    for algo in ("binomial", "bine_tree"):
        ex = build_executable(topo, cm, 0, 4096.0, algo=algo)
        assert ex.schedule.num_devices == 8
        assert ex.predicted_time > 0
        assert ex.num_groups == 1
    # binomial on a ring needs stride-2/4 relay hops
    ex = build_executable(topo, cm, 0, 4096.0, algo="binomial")
    assert ex.schedule.num_relay > 0


def test_non_tree_baseline_rejected():
    from repro.core import topology as T
    from repro.core.intersection import ConflictModel
    from repro.device import NotDeviceExecutable, build_executable

    topo = T.ring(8)
    cm = ConflictModel(topo)
    with pytest.raises(NotDeviceExecutable):
        build_executable(topo, cm, 0, 4e6, algo="srda")   # block exchanges


# ---------------------------------------------------------------------------
# Pallas round step (in-process; the CPU backend runs it in interpret mode)
# ---------------------------------------------------------------------------

def test_pallas_round_step_matches_oracle():
    import jax.numpy as jnp
    from repro.device.pallas_step import round_step

    rng = np.random.RandomState(0)
    buf = jnp.asarray(rng.rand(6, 16).astype(np.float32))
    rec = jnp.asarray(rng.rand(16).astype(np.float32))
    for (r_idx, r_ok, s_idx, s_ok) in [(2, True, 4, True), (0, False, 5, True),
                                       (3, True, 0, False),
                                       (1, False, 2, False)]:
        b0, v0 = round_step(buf, rec, r_idx, r_ok, s_idx, s_ok,
                            use_pallas=False)
        b1, v1 = round_step(buf, rec, r_idx, r_ok, s_idx, s_ok,
                            use_pallas=True)
        np.testing.assert_array_equal(np.asarray(b0), np.asarray(b1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


@pytest.mark.parametrize("r_ok", [True, False])
@pytest.mark.parametrize("s_ok", [True, False])
def test_pallas_round_step_matches_oracle_on_tiled_rows(r_ok, s_ok):
    """A buffer whose rows are (P, 128) tiles, as ``_pad_packets`` shapes
    long rows: the kernel indexes dimension 0 with the row whole."""
    import jax.numpy as jnp
    from repro.device.pallas_step import round_step, round_step_ref

    rng = np.random.RandomState(1)
    buf = jnp.asarray(rng.rand(6, 16, 128).astype(np.float32))
    rec = jnp.asarray(rng.rand(16, 128).astype(np.float32))
    for r_idx, s_idx in [(2, 4), (5, 5), (0, 3)]:
        b0, v0 = round_step_ref(buf, rec, r_idx, r_ok, s_idx, s_ok)
        b1, v1 = round_step(buf, rec, r_idx, r_ok, s_idx, s_ok,
                            use_pallas=True)
        assert b1.shape == buf.shape and v1.shape == rec.shape
        np.testing.assert_array_equal(np.asarray(b0), np.asarray(b1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "int8", "uint8"])
def test_pallas_refuses_packed_row_dtypes(dtype):
    """Rows narrower than 32 bits cannot be indexed dynamically by Mosaic:
    the kernel says so itself, and never hands the step to jnp."""
    import jax.numpy as jnp
    from repro.device.pallas_step import round_step

    buf = jnp.zeros((16, 256), dtype)
    with pytest.raises(ValueError, match="packed several to a sublane"):
        round_step(buf, buf[0], 1, True, 2, True, use_pallas=True)
    # the jnp step takes every dtype
    round_step(buf, buf[0], 1, True, 2, True, use_pallas=False)


def test_pallas_refuses_buffers_past_the_vmem_budget():
    import jax
    import jax.numpy as jnp
    from repro.device.pallas_step import (VMEM_LIMIT_BYTES,
                                          check_kernel_limits,
                                          kernel_vmem_bytes, round_step)

    def step(buf):
        return round_step(buf, buf[0], 1, True, 2, True, use_pallas=True)

    rows = 8
    plen = VMEM_LIMIT_BYTES // (2 * 4 * (rows + 1))   # just over the edge
    plen += 1024
    for row in [(plen,), (plen // 128, 128)]:
        assert kernel_vmem_bytes((rows,) + row) > VMEM_LIMIT_BYTES
        with pytest.raises(ValueError, match="VMEM_LIMIT_BYTES"):
            jax.eval_shape(step, jax.ShapeDtypeStruct((rows,) + row,
                                                      jnp.float32))
    plen -= 2048
    for row in [(plen,), (plen // 128, 128)]:
        assert kernel_vmem_bytes((rows,) + row) <= VMEM_LIMIT_BYTES
        check_kernel_limits((rows,) + row, jnp.float32)
        jax.eval_shape(step, jax.ShapeDtypeStruct((rows,) + row,
                                                  jnp.float32))


def test_kernel_vmem_counts_each_row_shapes_own_tiles():
    """A 2-D buffer's rows share (8, 128) tiles; a (P, 128) row is tiled
    on its own, so a row of 9 sublanes takes 16."""
    from repro.device.pallas_step import kernel_vmem_bytes

    assert kernel_vmem_bytes((3, 200)) == 4 * (2 * 8 * 256 + 2 * 256)
    assert kernel_vmem_bytes((3, 9, 128)) == \
        4 * (2 * 3 * 16 * 128 + 2 * 16 * 128)
    assert kernel_vmem_bytes((3, 9, 128), itemsize=2) == \
        2 * (2 * 3 * 16 * 128 + 2 * 16 * 128)


@pytest.mark.parametrize("dtype, tile", [("float32", 1024),
                                         ("bfloat16", 2048)])
def test_pad_packets_rounds_rows_to_whole_tiles(dtype, tile):
    """Every row is rounded up to whole (sublane, 128) tiles and shaped
    (P, 128), from a row of one word to one just past 64 tiles. The
    payload leads the buffer, zeros follow."""
    import jax.numpy as jnp
    from repro.device.runner import _pad_packets

    rows = 3
    for plen, tiles in [(1, 1), (tile - 1, 1), (tile, 1), (tile + 1, 2),
                        (64 * tile + 1, 65)]:
        x = jnp.arange(rows * plen - 1).astype(dtype)
        buf = _pad_packets(x, rows)
        assert buf.shape == (rows, tiles * tile // 128, 128)
        flat = np.asarray(buf).reshape(-1)
        np.testing.assert_array_equal(flat[:x.size], np.asarray(x))
        assert not flat[x.size:].any()


# ---------------------------------------------------------------------------
# Device mesh and chip placement (in-process)
# ---------------------------------------------------------------------------

class _Chip:
    def __init__(self, coords):
        self.coords = coords


# jax.devices() order on a TPU v5e 2x2 host
V5E_2X2 = [_Chip(c) for c in ((0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0))]


def test_torus2d_edges_land_on_chip_links():
    from repro.core import topology as T
    from repro.device import node_coords

    coords = node_coords(T.torus2d(2, 2), V5E_2X2)
    assert coords == {0: (0, 0, 0), 1: (1, 0, 0), 2: (0, 1, 0), 3: (1, 1, 0)}


def test_ring4_has_edges_between_diagonal_chips():
    """Two of ring(4)'s four edges join diagonal chips: ``far_pairs``
    names them (both directions) and ``node_coords`` does not refuse
    them; torus2d(2, 2) has none."""
    from repro.core import topology as T
    from repro.device import far_pairs, node_coords

    topo = T.ring(4)
    far = far_pairs(topo.candidate_edges, node_coords(topo, V5E_2X2))
    assert sorted(far) == [(0, 3), (1, 2), (2, 1), (3, 0)]
    torus = T.torus2d(2, 2)
    assert far_pairs(torus.candidate_edges,
                     node_coords(torus, V5E_2X2)) == []
    with pytest.raises(ValueError, match="equal blocks"):
        node_coords(T.ring(6), V5E_2X2)


def test_device_mesh_error_names_both_counts():
    import jax
    from repro.device import device_mesh

    have = len(jax.devices())
    with pytest.raises(RuntimeError) as e:
        device_mesh(have + 3)
    msg = str(e.value)
    assert f"needs {have + 3} devices" in msg
    assert f"{jax.devices()[0].platform} platform has {have}" in msg


# ---------------------------------------------------------------------------
# Config + shim (in-process)
# ---------------------------------------------------------------------------

def test_device_config_validation():
    from repro.core.simconfig import DeviceConfig, SimConfig

    cfg = DeviceConfig(mesh_shape=[2, 4])
    assert cfg.mesh_shape == (2, 4)          # normalized to a tuple
    with pytest.raises(ValueError):
        DeviceConfig(dtype="float64")
    with pytest.raises(ValueError):
        DeviceConfig(mesh_shape=(0, 8))
    with pytest.raises(ValueError):
        DeviceConfig(axis="")
    with pytest.raises(TypeError):
        SimConfig(device={"axis": "dev"})
    sc = SimConfig(device=DeviceConfig())
    assert sc.device.axis == "dev"


def test_collectives_shim_warns_once_and_forwards():
    from repro.collectives import bbs_collective as shim
    from repro.core import topology as T
    from repro.core.bbs import build_plan
    from repro import device

    plan = build_plan(T.ring(8), root=0)
    shim.reset_moved_warning()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        s0 = shim.make_device_schedule(plan.candidates[0].pipeline, 8)
        s1 = shim.make_device_schedule(plan.candidates[0].pipeline, 8)
    deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert len(deps) == 1, "shim must warn exactly once per process"
    assert "repro.device" in str(deps[0].message)
    # forwards to the real implementation
    ref = device.make_device_schedule(plan.candidates[0].pipeline, 8)
    assert s0.perms == ref.perms and s1.perms == ref.perms
    assert isinstance(s0, device.DeviceSchedule)


# ---------------------------------------------------------------------------
# Calibration artifacts (in-process)
# ---------------------------------------------------------------------------

def test_fit_hockney_recovers_known_constants():
    from repro.device.calibrate import _fit_hockney

    alpha, beta = 2e-5, 40e9
    sizes = [1 << 10, 8 << 10, 64 << 10, 1 << 20]
    times = [alpha + s / beta for s in sizes]
    a, b, resid = _fit_hockney(sizes, times)
    assert abs(a - alpha) / alpha < 1e-6
    assert abs(b - beta) / beta < 1e-6
    assert resid < 1e-12


def test_calibrated_cost_json_roundtrip(tmp_path):
    from repro.device.calibrate import CalibratedCost

    cost = CalibratedCost(classes={"tpu_ici": (1.5e-5, 45e9)},
                          meta={"backend": "cpu", "emulated": True})
    path = cost.save(str(tmp_path / "calibration.json"))
    c2 = CalibratedCost.load(path)
    assert c2.classes == cost.classes and c2.meta == cost.meta
    assert c2.round_time("tpu_ici", 45e9) == pytest.approx(1.0 + 1.5e-5)
    with pytest.raises(ValueError):
        CalibratedCost.from_dict({"magic": "something-else", "classes": {}})


def test_apply_calibration_changes_fingerprint():
    from repro.core import topology as T
    from repro.core.routing import topology_fingerprint
    from repro.device import CalibratedCost, apply_calibration

    topo = T.ring(8)
    cost = CalibratedCost(classes={"tpu_ici": (1e-5, 5e10)})
    t2 = apply_calibration(topo, cost)
    assert topology_fingerprint(t2) != topology_fingerprint(topo)
    assert t2.latency((0, 1)) == pytest.approx(1e-5)
    # plans build cleanly against the calibrated fabric
    from repro.core.bbs import build_plan
    assert build_plan(t2, root=0).candidates


def test_planstore_calibration_roundtrip(tmp_path):
    from repro.core import topology as T
    from repro.core.planstore import (CalibrationKey, PlanStore,
                                      StalePlanError)
    from repro.device import CalibratedCost

    topo = T.ring(8)
    store = PlanStore(str(tmp_path))
    key = CalibrationKey.for_topology(topo, "cpu", 8)
    cost = CalibratedCost(classes={"tpu_ici": (1e-5, 5e10)},
                          meta={"backend": "cpu"})
    path = store.store_calibration(key, cost)
    c2, meta = store.load_calibration(key)
    assert c2.classes == cost.classes
    assert meta["backend"] == "cpu" and meta["num_devices"] == 8
    # prune recognizes the artifact as canonical
    assert store.prune() == []
    assert os.path.exists(path)
    # a different environment is a different artifact
    with pytest.raises(FileNotFoundError):
        store.load_calibration(CalibrationKey.for_topology(topo, "tpu", 8))
    # a corrupted artifact raises StalePlanError (and prune removes it)
    with open(path, "wb") as f:
        f.write(b"garbage")
    with pytest.raises(StalePlanError):
        store.load_calibration(key)
    assert store.prune() == [path]


def test_roofline_consumes_calibration(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        import roofline
    finally:
        sys.path.pop(0)
    from repro.device import CalibratedCost

    assert roofline.load_calibration(str(tmp_path / "missing.json")) is None
    assert roofline.link_bandwidth(None) == roofline.LINK_BW
    cost = CalibratedCost(classes={"tpu_ici": (1e-5, 45e9)})
    p = cost.save(str(tmp_path / "calibration.json"))
    c = roofline.load_calibration(p)
    assert roofline.link_bandwidth(c) == pytest.approx(45e9)
    # all-port collective term: 2D torus has 4 concurrent links per chip
    assert roofline.links_per_chip("pod16x16") == 4
    rec = {"chips": 256, "mesh": "pod16x16", "flops": 1e12,
           "dot_bytes": 1e9, "collective_bytes": {"all-reduce": 4e8},
           "memory": {"peak_bytes": 1 << 30},
           "arch": "llama3.2-3b", "shape": "train_4k"}
    row = roofline.roofline_row(rec, c)
    assert row["t_collective"] == pytest.approx(4e8 / (45e9 * 4))


# ---------------------------------------------------------------------------
# End-to-end on the emulated 8-device mesh (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_executable_end_to_end_bit_exact():
    """Acceptance: BBS and Bine plans deliver bit-identically on two
    fabrics x two message sizes through api.compile(...).executable(...)."""
    run_multidevice("""
        import numpy as np, jax.numpy as jnp
        from repro import api
        from repro.core import topology as T
        for mk in (lambda: T.ring(8), lambda: T.hypercube(3)):
            topo = mk()
            model = api.compile(topo)
            for nbytes in (1 << 12, 1 << 16):
                x = jnp.asarray(np.random.RandomState(7)
                                .rand(nbytes // 4).astype(np.float32))
                for algo in ("bbs", "bine_tree"):
                    ex = model.executable(root=0, nbytes=nbytes, algo=algo)
                    chk = ex.verify(x)
                    assert chk.ok, (topo.name, nbytes, algo, chk.missing)
    """)


@pytest.mark.slow
def test_executable_nonzero_root_and_pallas():
    """Relabeled (PlanServer) plans execute correctly from non-canonical
    roots, and the Pallas round step (interpreted on the CPU) is
    bit-identical."""
    run_multidevice("""
        import numpy as np, jax.numpy as jnp
        from repro import api
        from repro.core import topology as T
        from repro.core.simconfig import DeviceConfig, SimConfig
        model = api.compile(T.ring(8), server=True)
        x = jnp.asarray(np.random.RandomState(3)
                        .rand(2048).astype(np.float32))
        for root in (0, 3, 5):
            ex = model.executable(root=root, nbytes=8192)
            assert ex.verify(x).ok, root
        cfg = SimConfig(device=DeviceConfig(use_pallas=True))
        ex = model.executable(root=2, nbytes=8192, config=cfg)
        assert ex.device.use_pallas
        assert ex.verify(x).ok
    """)


def test_lower_is_the_program_run_dispatches():
    """``ExecutablePlan.lower`` lowers the jitted program that ``run``
    calls: compiled and called on the input ``run`` builds, it delivers
    what ``run`` delivers, and its optimized HLO carries the round step's
    scope."""
    out = run_multidevice("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import api
        from repro.core import topology as T
        ex = api.compile(T.torus2d(2, 2)).executable(0, 4096)
        mesh = ex.mesh()
        x = np.arange(1024, dtype=np.float32)
        compiled = ex.lower(jnp.asarray(x), mesh).compile()
        split, _ = ex._split_input(jnp.asarray(x), mesh)
        got = np.asarray(compiled(split))
        ran = np.asarray(ex.run(jnp.asarray(x), mesh))
        print(json.dumps({"same": bool((got == ran).all()),
                          "delivered": bool((got == x[None]).all()),
                          "step": "bcast.step" in compiled.as_text()}))
    """, devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"same": True, "delivered": True, "step": True}


# (fabric, mode, root, devices, payload bytes): the payload is made on
# device 0, as the benchmark makes it on the root's chip
ROOT_CHIP_RUNS = {
    "torus_root0": ("torus2d(2, 2)", "full_duplex", 0, 4, 1 << 14),
    "torus_root3": ("torus2d(2, 2)", "full_duplex", 3, 4, 1 << 14),
    "dragonfly_fold4": ('by_name("dragonfly", 128)', "full_duplex", 0, 4,
                        1 << 14),
    "dragonfly_fold1": ('by_name("dragonfly", 128)', "full_duplex", 0, 1,
                        1 << 14),
}


@pytest.mark.parametrize("case", sorted(ROOT_CHIP_RUNS))
def test_run_reads_the_payload_on_the_root_chip_only(case):
    """``run`` delivers the payload bit for bit to every node from a
    payload made on device 0. The program it dispatches takes the payload
    split over the mesh axis, one shard of the payload's shape a device
    (not replicated), and ``counters`` counts a move only where the root's
    chip is not device 0: then the payload's bytes, once a run."""
    fabric, mode, root, devices, nbytes = ROOT_CHIP_RUNS[case]
    out = run_multidevice(f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import api
        from repro.core import topology as T
        ex = api.compile(T.{fabric}, "{mode}").executable({root}, {nbytes})
        mesh = ex.mesh(jax.devices()[:{devices}])
        x = jax.device_put(np.random.RandomState(11).rand({nbytes // 4})
                           .astype(np.float32), jax.devices()[0])
        outs = [np.asarray(ex.run(x, mesh)) for _ in range(2)]
        split, _ = ex._split_input(x, mesh)
        sh = ex.lower(x, mesh).compile().input_shardings[0][0]
        print(json.dumps({{
            "exact": [o.shape[0] == ex.topo.num_nodes and all(
                o[v].tobytes() == np.asarray(x).tobytes()
                for v in range(o.shape[0])) for o in outs],
            "spec": sh.spec == P("dev"),
            "devices": len(sh.device_set),
            "shard": list(sh.shard_shape(split.shape)),
            "split": sh == split.sharding,
            "replicated": sh.is_fully_replicated,
            "counters": ex.counters}}))
    """, devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    moves = 2 if root else 0
    assert got == {"exact": [True, True], "spec": True, "devices": devices,
                   "shard": [nbytes // 4], "split": True,
                   "replicated": devices == 1,
                   "counters": {"runs": 2, "payload_moves": moves,
                                "payload_moved_bytes": moves * nbytes}}


def test_run_takes_a_replicated_payload_from_the_root_chips_shard():
    """A payload replicated over the mesh is not moved: the root chip's
    own shard, the same buffer, is the program's input there; the other
    chips' shards are placeholders kept between runs."""
    out = run_multidevice("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import api
        from repro.core import topology as T
        ex = api.compile(T.torus2d(2, 2)).executable(3, 4096)
        mesh = ex.mesh()
        x = jax.device_put(np.arange(1024, dtype=np.float32),
                           NamedSharding(mesh, P()))
        ptr = lambda a, d: next(s.data.unsafe_buffer_pointer()
                                for s in a.addressable_shards
                                if s.device == d)
        devs = list(mesh.devices.flat)
        first, _ = ex._split_input(x, mesh)
        again, _ = ex._split_input(x, mesh)
        out = np.asarray(ex.run(x, mesh))
        print(json.dumps({
            "exact": bool((out == np.asarray(x)[None]).all()),
            "root_shard": ptr(first, devs[3]) == ptr(x, devs[3]),
            "kept": [ptr(first, d) == ptr(again, d) for d in devs[:3]],
            "counters": ex.counters}))
    """, devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"exact": True, "root_shard": True,
                   "kept": [True] * 3,
                   "counters": {"runs": 1, "payload_moves": 0,
                                "payload_moved_bytes": 0}}


# a payload whose rows (12 of 87,357 f32 on torus2d(2, 2)) span many
# tiles and are not a whole number of them, and one whose rows are shorter
# than a tile
ROW_SIZES = {"multi_tile": (1 << 20) - 300, "sub_tile": 1000}


@pytest.mark.parametrize("size", sorted(ROW_SIZES))
def test_broadcasts_deliver_bit_exact_on_tiled_rows(size):
    """BBS on torus2d(2, 2) and the chain baseline (8 packets), which
    share ``_pad_packets``, deliver the payload bit for bit from roots 0
    and 3; the BBS program's packet buffer has the row shape
    ``_pad_packets`` chose, whole (8, 128) tiles."""
    out = run_multidevice(f"""
        import json
        import jax.numpy as jnp, numpy as np
        from repro import api
        from repro.core import topology as T
        from repro.device.runner import (_pad_packets, chain_broadcast,
                                         device_mesh)
        words = {ROW_SIZES[size]}
        x = np.random.RandomState(5).rand(words).astype(np.float32)
        model = api.compile(T.torus2d(2, 2))
        mesh = device_mesh(4)
        xc = x[:8 * words // 12]
        got = {{"chain_row": list(_pad_packets(jnp.asarray(xc), 8).shape[1:])}}
        for root in (0, 3):
            ex = model.executable(root, 4 * words)
            rows = ex.num_groups * ex.schedule.K
            shape = _pad_packets(jnp.asarray(x), rows).shape
            txt = ex.lower(jnp.asarray(x)).as_text()
            chain = np.asarray(chain_broadcast(jnp.asarray(xc), mesh, "dev",
                                               root=root))
            got[root] = {{"ok": ex.verify(x).ok, "rows": rows,
                         "row": list(shape[1:]),
                         "in_program": "x".join(map(str, shape)) + "xf32"
                                       in txt,
                         "chain": [chain[v].tobytes() == xc.tobytes()
                                   for v in range(4)]}}
        print(json.dumps(got))
    """, devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    words = ROW_SIZES[size]
    for root in ("0", "3"):
        assert got[root]["ok"] and got[root]["in_program"], got
        assert got[root]["chain"] == [True] * 4, got
        p, lanes = got[root]["row"]
        assert lanes == 128 and p % 8 == 0
        plen = -(-words // got[root]["rows"])
        assert plen <= p * 128 < plen + 1024
        if size == "sub_tile":
            assert p == 8
    p, lanes = got["chain_row"]
    assert lanes == 128 and p % 8 == 0


def test_runners_name_their_phases():
    """The lowered BBS (jnp and Pallas round step), binomial and chain
    programs carry the shared phase scopes."""
    out = run_multidevice("""
        import json, re
        import jax, jax.numpy as jnp
        from repro import api
        from repro.core import topology as T
        from repro.device import bbs_broadcast
        from repro.device.runner import (binomial_broadcast, chain_broadcast,
                                         device_mesh)
        mesh = device_mesh(4)
        ex = api.compile(T.torus2d(2, 2)).executable(0, 4096)
        x = jnp.arange(1024, dtype=jnp.float32)
        progs = {
            "bbs": lambda v: bbs_broadcast(v, mesh, "dev", ex.schedule,
                                           ex.num_groups),
            "bbs_pallas": lambda v: bbs_broadcast(
                v, mesh, "dev", ex.schedule, ex.num_groups,
                use_pallas=True),
            "binomial": lambda v: binomial_broadcast(v, mesh, "dev"),
            "chain": lambda v: chain_broadcast(v, mesh, "dev"),
        }
        print(json.dumps({k: sorted(set(re.findall(
            r"bcast\\.[a-z]+",
            jax.jit(f).lower(x).as_text(debug_info=True))))
            for k, f in progs.items()}))
    """, devices=4)
    scopes = json.loads(out.strip().splitlines()[-1])
    shared = ["bcast.place", "bcast.unstack"]
    assert scopes["binomial"] == scopes["chain"] == shared
    bbs = sorted(shared + ["bcast.cycle", "bcast.step"])
    assert scopes["bbs"] == scopes["bbs_pallas"] == bbs


@pytest.mark.slow
def test_calibration_prediction_error_bound():
    """Fitted Hockney constants predict the measured cycle time within the
    35% subprocess tolerance (the committed bench floor holds the tighter
    15% bound on the quiet CI runner profile)."""
    out = run_multidevice("""
        import warnings
        warnings.filterwarnings('ignore', message='.*donated.*')
        from repro import api
        from repro.core import topology as T
        from repro.device import calibrate, prediction_report
        topo = T.ring(8)
        model = api.compile(topo)
        ex = model.executable(root=0, nbytes=1 << 16)
        mesh = ex.mesh()
        cost = calibrate(topo, mesh, sizes=(1 << 10, 8 << 10, 64 << 10),
                         iters=16, reps=3)
        assert cost.meta['emulated'] and cost.meta['backend'] == 'cpu'
        a, b = cost.classes[next(iter(cost.classes))]
        assert a >= 0 and b > 0
        rows = prediction_report([ex], cost, mesh=mesh, reps=3)
        print('PRED_ERR', rows[0].rel_err)
    """)
    err = float(out.split("PRED_ERR")[1].split()[0])
    assert err <= 0.35, f"prediction error {err:.1%} out of bounds"
