"""The reduction from trace to per-layer metrics: on a hand-made trace whose
answers are known, and on a trace recorded on a TPU v5e."""

import json
import pathlib

import pytest

import bench
from trace_reduce import Trace, merge

DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9,
         "hbm_bytes": 16e9}


def hand_trace():
    """Two chips over a 1000 ns window holding two requests. Chip 0 makes
    the payload (a ``bench_`` module) and runs the program; chip 1 runs the
    program only."""
    ops0 = [("rng", 0, 100), ("fusion.1", 200, 100),
            ("collective-permute-start.1", 300, 10),
            ("collective-permute-done.1", 310, 90),
            ("rng", 500, 100), ("fusion.1", 650, 150),
            ("collective-permute-start.1", 800, 10),
            ("collective-permute-done.1", 810, 40)]
    mods0 = [("jit_bench_payload_fn(1)", 0, 100), ("jit_run(2)", 200, 200),
             ("jit_bench_payload_fn(1)", 500, 100), ("jit_run(2)", 650, 200)]
    ops1 = [("fusion.1", 200, 100), ("collective-permute-start.1", 300, 10),
            ("collective-permute-done.1", 310, 90),
            ("fusion.1", 650, 150), ("collective-permute-start.1", 800, 10),
            ("collective-permute-done.1", 810, 40)]
    mods1 = [("jit_run(2)", 200, 200), ("jit_run(2)", 650, 200)]
    host = [("bench.payload", 0, 150), ("bench.request", 150, 20),
            ("bench.wait", 170, 250), ("bench.payload", 420, 200),
            ("bench.request", 620, 20), ("bench.wait", 640, 260)]
    return Trace([{"ops": ops0, "modules": mods0},
                  {"ops": ops1, "modules": mods1}], host, 1000.0)


def test_merge():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_program_ops():
    tr = hand_trace()
    assert tr.busy_ns(0) == 600 and tr.busy_ns(1) == 400
    assert tr.busy_s() == pytest.approx(500e-9)
    assert tr.idle_share() == pytest.approx(0.5)
    assert tr.program_busy_ns(0) == 400 and tr.program_busy_ns(1) == 400
    assert tr.permute_ns(0) == 150 and tr.permute_count(0) == 2


def test_window_ends_where_every_chip_is_traced():
    """Both chips' ops end at 850 ns, before the second request's wait
    (900 ns): the window is cut back to the first request's end."""
    tr = hand_trace()
    assert tr.request_count() == 2
    tr.window_ns = tr.covered_ns()
    assert tr.window_ns == 420 and tr.request_count() == 1
    tr.devices[1]["ops"] = tr.devices[1]["ops"][:3]    # chip 1 stops early
    assert tr.covered_ns() == 420


def test_permutes_are_those_inside_the_window():
    """Count and time come from the same permutes: where the window is cut
    back while a chip's ops go on, the permutes after the cut count in
    neither, so the mean permute stays what it is."""
    tr = hand_trace()
    tr.window_ns = tr.covered_ns()                    # 420: one request
    assert tr.permutes(0) == [(300, 400)]
    assert tr.permute_count(1) == 1 and tr.permute_ns(1) == 100
    reqs = [(150e-9, 420e-9, 1000)]
    run = _run(tr, "bcast2x2_ddp_250m", reqs,
               {"message_bytes": 1000, "row_bytes": 100})
    assert _read(run, "ppermute_roofline") == pytest.approx(100 * 0.5 / 100)
    assert _read(run, "ppermute_ms") == pytest.approx(100e-6)


def test_permute_spans_its_start_to_its_done():
    """Work run between a permute's halves does not shorten it: the row is
    in flight from the start's start to the done's end."""
    tr = hand_trace()
    tr.devices[0]["ops"][2:4] = [("collective-permute-start.1", 300, 10),
                                 ("fusion.2", 310, 80),
                                 ("collective-permute-done.1", 390, 10)]
    assert tr.permutes(0)[0] == (300, 400)
    # a done whose start was not recorded counts alone
    del tr.devices[1]["ops"][1]
    assert tr.permutes(1)[0] == (310, 400)


def test_breakdown_names_gaps_by_host_span():
    bd = hand_trace().breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    # chip 1 is idle from 400 to 650 while the host makes a payload
    assert bd["idle_gaps"][0] == ["bench.payload (chip 1)",
                                  pytest.approx(250e-9)]
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10


def _run(tr, cell, requests, info):
    return bench.Run(cell=bench.load_cell(cell), peaks=PEAKS,
                     setup={}, requests=requests, window=(0.0, tr.window_s),
                     info=info, trace=tr)


def _read(run, name):
    path = run.cell.bench_path("metrics", f"{name}.py")
    return bench.load_module(path).read(run)


def test_readers_on_the_hand_trace():
    tr = hand_trace()
    reqs = [(150e-9, 420e-9, 1000), (620e-9, 900e-9, 1000)]
    run = _run(tr, "bcast2x2_ddp_250m", reqs,
               {"message_bytes": 1000, "row_bytes": 100})
    assert _read(run, "device_idle_share.bcast") == pytest.approx(50.0)
    assert _read(run, "ppermute_ms") == pytest.approx(75e-6)
    assert _read(run, "local_ms.bcast") == pytest.approx(125e-6)
    # 100 B at 200 GB/s is 0.5 ns, over a mean permute of 75 ns
    assert _read(run, "ppermute_roofline") == pytest.approx(100 * 0.5 / 75)
    # 1000 B at 200 GB/s is 5 ns, over a mean request of 275 ns
    assert _read(run, "bcast_ici_peak_share") == pytest.approx(
        100 * 5 / 275)


def test_readers_return_nothing_without_a_trace():
    run = _run(hand_trace(), "node_ddp_250m", [], {})
    run.trace = None
    for m in run.cell.per_layer:
        if m["source"] == "device_trace":
            assert _read(run, m["name"]) is None


def test_trim_and_load(tmp_path):
    tr = hand_trace()
    tr.trim(str(tmp_path / "t.json.gz"), requests=1)
    back = Trace.load(str(tmp_path / "t.json.gz"))
    assert back.window_ns == 480          # second payload to its wait's end
    assert back.chips == 2
    assert [e[0] for e in back.host] == ["bench.payload", "bench.request",
                                         "bench.wait"]
    assert back.requests() == [(pytest.approx(200e-9), pytest.approx(480e-9))]


RECORDED = sorted(DATA.glob("trace_*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_trace(path):
    """A few requests traced on a TPU v5e: every reader of the cell finds
    a number, and no share passes 100%."""
    meta = json.loads(path.with_suffix("").with_suffix(".meta.json")
                      .read_text())
    tr = Trace.load(str(path))
    reqs = [(a, b, meta["info"]["message_bytes"]) for a, b in tr.requests()]
    run = _run(tr, meta["workload"], reqs, meta["info"])
    assert 0 < tr.busy_s() <= tr.window_s
    for m in run.cell.per_layer:
        if m["source"] != "device_trace" and m["unit"] != "%":
            continue
        v = _read(run, m["name"])
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
    bd = tr.breakdown()
    assert bd["device_ops"] and bd["idle_gaps"]
