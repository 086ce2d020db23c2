"""The folded broadcast's cells (``dfly128_fold1_16m``, ``dfly128_fold4_64m``)
end to end at a tiny size on CPU devices: sound runs come out correct; the
control, and each fault planted in the timed path, come out not correct.
And the cells' new readers on a hand-made trace whose answers are known
and on a trace recorded from the four-chip cell on a TPU v5e, read against
the description of the program that ran there, stored beside it."""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

import bench
import folded
from trace_reduce import Trace
from repro.device import runner

TINY = {"message_bytes": 1 << 14}
SEED = 2 ** 33 + 7          # wider than 32 bits, as the driver's seeds are
CELLS = ["dfly128_fold1_16m", "dfly128_fold4_64m"]
NEW = ["local_move_ms.fold", "fold_step_roofline", "cross_roofline.fold"]
DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9,
         "hbm_bytes": 16e9}


def run(cell, seed=SEED, **kw):
    return bench.run_cell(cell, seed, 0.3, False, require_tpu=False,
                          traffic_overrides=TINY, **kw)


@pytest.fixture(autouse=True)
def fresh_traces():
    """Traced programs are cached by function: a planted fault must be
    traced anew, and must not outlive its test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [SEED, 13])
def test_sound_run_is_correct(cell, seed):
    res = run(cell, seed)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    for m in bench.load_cell(cell).end_to_end:
        assert res["metrics"][m["name"]]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The reference one precision lower (bfloat16 for float32)."""
    assert not run(cell, control=True)["correct"]


runner_unstack = runner._unstack


def node_unwritten(out, x):
    """The last node's copy is never written: it comes back as zeros."""
    return runner_unstack(out.at[-1].set(0), x)


def bit_flipped_per_node(out, x):
    """One bit of every node's copy flipped."""
    bits = jax.lax.bitcast_convert_type(out, jnp.uint32)
    bits = bits.at[:, 0].set(bits[:, 0] ^ 1)
    return runner_unstack(jax.lax.bitcast_convert_type(bits, out.dtype), x)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [node_unwritten, bit_flipped_per_node])
def test_fault_in_the_copies_is_not_correct(monkeypatch, cell, fault):
    monkeypatch.setattr(runner, "_unstack", fault)
    assert not run(cell)["correct"]


def test_no_cross_chip_permute_is_not_correct(monkeypatch):
    """Each chip keeps the stack it would have sent instead of receiving
    one."""
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis, perm: x)
    assert not run("dfly128_fold4_64m")["correct"]


# -- readers ---------------------------------------------------------------

BODY = "jit(run)/shard_map/bcast.cycle/while/body/"
PROGRAM = {
    "module": "jit_run",
    "scopes": {
        "gather.1": BODY + "bcast.step/gather",
        "scatter.1": BODY + "bcast.local/scatter",
        "collective-permute-start": BODY + "ppermute",
        "collective-permute-done": BODY + "ppermute",
        "scatter.2": BODY + "bcast.step/scatter",
        "slice.1": "jit(run)/bcast.unstack/slice",
    },
    "perms": [],
}


def hand_trace():
    """One chip, one request: a gather (10-20), the local hand-over (20-35)
    overlapping a permute in flight (30-50), a write of the received stack
    (50-60), the unstacking (60-70)."""
    ops = [("gather.1", 10, 10), ("scatter.1", 20, 15),
           ("collective-permute-start", 30, 2),
           ("collective-permute-done", 40, 10), ("scatter.2", 50, 10),
           ("slice.1", 60, 10)]
    return Trace([{"ops": ops, "modules": [("jit_run(3)", 5, 70)]}],
                 [("bench.request", 0, 2), ("bench.wait", 2, 78)], 100.0)


def _run(tr, cell, info, program):
    reqs = [(a, b, info["message_bytes"]) for a, b in tr.requests()]
    run = bench.Run(cell=bench.load_cell(cell), peaks=PEAKS, setup={},
                    requests=reqs, window=(0.0, tr.window_s), info=info,
                    trace=tr)
    folded.use_program(run, program)
    return run


def _read(run, name):
    return bench.load_module(
        run.cell.bench_path("metrics", f"{name}.py")).read(run)


def test_readers_on_the_hand_trace():
    # 35 ns under bcast.step or bcast.local; 20 ns of a permute in flight
    info = {"message_bytes": 1000, "least_bytes_per_chip": 819e9 * 35e-9 / 2,
            "bytes_per_permute": 200e9 * 20e-9 / 4}
    run = _run(hand_trace(), "dfly128_fold4_64m", info, PROGRAM)
    assert _read(run, "local_move_ms.fold") == pytest.approx(10e-6)
    assert _read(run, "fold_step_roofline") == pytest.approx(50.0)
    assert _read(run, "cross_roofline.fold") == pytest.approx(25.0)
    assert _read(run, "ppermute_ms") == pytest.approx(20e-6)
    run.trace = None
    assert [_read(run, n) for n in NEW] == [None] * 3


RECORDED = DATA / "fold4_trace.json.gz"


def test_recorded_trace():
    """A request of ``dfly128_fold4_64m`` traced on a TPU v5e 2x2 (run.py
    ``--dump-trace``), read against the description of the program that
    ran there (``fold4_trace.program.json``, written on the chip after the
    run by ``folded.describe``): every per-layer reader of the cell that
    reads the trace finds a number, and no share passes 100%."""
    meta = json.loads((DATA / "fold4_trace.meta.json").read_text())
    program = json.loads((DATA / "fold4_trace.program.json").read_text())
    tr = Trace.load(str(RECORDED))
    run = _run(tr, meta["workload"], meta["info"], program)
    assert 0 < tr.busy_s() <= tr.window_s
    for m in run.cell.per_layer:
        if m["source"] != "device_trace":
            continue
        v = _read(run, m["name"])
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
