"""The broadcast's phases read from a trace (``phases.py``): the partition
of program busy time, the pre-launch gap and the clock-offset bounds on
hand-made traces whose answers are known, and the phase metrics on the
traces recorded on a TPU v5e, with every reader that was there before them
returning what it returned."""

import json
import pathlib

import pytest

import bench
import phases
from trace_reduce import Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAKS = {"hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9,
         "hbm_bytes": 16e9}
PHASES = ["place_ms.bcast", "step_ms.bcast", "unstack_ms.bcast",
          "other_ms.bcast"]
NEW = PHASES + ["prelaunch_ms.bcast"]

CYCLE = "jit(run)/shard_map/bcast.cycle/while"
PROGRAM = {
    "module": "jit_run",
    "scopes": {
        "pad.1": "jit(run)/bcast.place/jit(_pad)/pad",
        "while.1": CYCLE,
        "dus.1": CYCLE + "/body/closed_call/bcast.step/dynamic_update_slice",
        "collective-permute-start": CYCLE + "/body/closed_call/ppermute",
        "collective-permute-done": CYCLE + "/body/closed_call/ppermute",
        "fusion.2": "",
        "copy.1": "",
        "slice.1": "jit(run)/bcast.unstack/slice",
    },
    "perms": [[[0, 1], [1, 0]]],
}


def one_chip(ops, window=100.0):
    """One chip running one ``jit_run`` program over ``[0, 100)``."""
    return Trace([{"ops": ops, "modules": [("jit_run(7)", 0, 100)]}],
                 [("bench.request", -5, 1), ("bench.wait", -4, 104)],
                 window)


def partition_trace():
    """A cycle loop (``while.1``, 10-90) holding a round step (20-30), a
    permute (30-50) with an unscoped op under it (32-42), and an unscoped
    copy (55-65); the pad before it (0-5) and the slice after it (92-98)."""
    return one_chip([("pad.1", 0, 5), ("while.1", 10, 80), ("dus.1", 20, 10),
                     ("collective-permute-start", 30, 2), ("fusion.2", 32, 10),
                     ("collective-permute-done", 42, 8), ("copy.1", 55, 10),
                     ("slice.1", 92, 6)])


def test_permute_in_flight_wins_then_the_innermost_op():
    split = phases.Phases(partition_trace(), PROGRAM).split_ns(0)
    assert split == {"bcast.place": 5, "bcast.step": 10, "permute": 20,
                     "other": 10, "bcast.cycle": 25 + 5 + 10,
                     "bcast.unstack": 6}


def _run(tr, info=None, program=PROGRAM, cell="bcast2x2_ddp_250m"):
    """A run of ``tr`` whose program is described by ``program`` (not
    compiled anew); ``None`` for a run without a trace."""
    reqs = [(a, b, 1000) for a, b in tr.requests()]
    run = bench.Run(cell=bench.load_cell(cell), peaks=PEAKS, setup={},
                    requests=reqs, window=(0.0, tr.window_s),
                    info=info or {"message_bytes": 1000, "row_bytes": 100},
                    trace=tr)
    if program is None:
        run.trace = None
    else:
        run._phases = phases.Phases(tr, program)
    return run


def _read(run, name):
    path = run.cell.bench_path("metrics", f"{name}.py")
    return bench.load_module(path).read(run)


def test_phase_readers_partition_local_time():
    """The unscoped ops and the loop's own time go to ``other``; the four
    phases sum to ``local_ms.bcast``."""
    run = _run(partition_trace())
    got = {m: _read(run, m) for m in PHASES}
    assert got == pytest.approx({"place_ms.bcast": 5e-6,
                                 "step_ms.bcast": 10e-6,
                                 "unstack_ms.bcast": 6e-6,
                                 "other_ms.bcast": 50e-6})
    assert sum(got.values()) == pytest.approx(_read(run, "local_ms.bcast"))


def test_no_phase_without_the_scopes():
    """A program without scopes (built before them), or a run without a
    trace, reads nothing."""
    bare = dict(PROGRAM, scopes={k: "" for k in PROGRAM["scopes"]})
    run = _run(partition_trace(), program=bare)
    assert all(_read(run, m) is None for m in PHASES)
    run = _run(partition_trace(), program=None)
    assert all(_read(run, m) is None for m in NEW)


def test_a_trace_of_another_program_is_an_error():
    """Scopes described for instructions the traced runs do not have: the
    description is not of the program that ran, and reading on would
    drop the metrics without a word."""
    other = dict(PROGRAM, scopes={"pad.1": PROGRAM["scopes"]["pad.1"]})
    run = _run(partition_trace(), program=other)
    for m in PHASES:
        with pytest.raises(ValueError, match="not instructions"):
            _read(run, m)


def test_a_program_from_before_the_scopes_is_described_by_its_perms():
    """A plan without ``lower`` (the program before its phase scopes) is
    described by its schedule alone: the phases read nothing, the
    pre-launch gap and the clock bounds still read."""
    from types import SimpleNamespace
    ex = SimpleNamespace(schedule=SimpleNamespace(perms=[[(0, 1), (1, 0)]]))
    program = phases.describe_program(ex, None, None)
    assert program == {"module": None, "scopes": {},
                       "perms": [[[0, 1], [1, 0]]]}
    run = _run(prelaunch_trace(), program=program)
    assert all(_read(run, m) is None for m in PHASES)
    assert _read(run, "prelaunch_ms.bcast") == pytest.approx(36.75e-6)


def prelaunch_trace():
    """Two requests launched at 0 and 200. Chip 0's program runs start at
    10 and 205, their first ops at 30 and 240; chip 1's runs start early
    (5, 202) and wait, their first ops at 31 and 241. A run before the
    window (its launch at -50) is not counted."""
    host = [("bench.request", -50, 1), ("bench.wait", -49, 40),
            ("bench.request", 0, 2), ("bench.wait", 2, 150),
            ("bench.request", 200, 2), ("bench.wait", 202, 98)]
    def chip(t0, t1, first0, first1):
        return {"modules": [("jit_run(7)", -45, 30), ("jit_run(7)", t0, 100),
                            ("jit_run(7)", t1, 80)],
                "ops": [("pad.1", -40, 5), ("pad.1", first0, 5),
                        ("slice.1", 100, 5), ("pad.1", first1, 5),
                        ("slice.1", 270, 5)]}
    return Trace([chip(10, 205, 30, 240), chip(5, 202, 31, 241)], host, 300)


def test_prelaunch_pairs_each_launch_with_the_run_it_started():
    """Raw gaps 35 and 36 on average; the host bounds chip 0's clock offset
    to [-6, 5] (a run ends 6 before its wait does, another starts 5 after
    its launch) and chip 1's to [-6, 2], so their gaps read 0.5 and 2
    longer once corrected by the midpoints."""
    ph = phases.Phases(prelaunch_trace())
    assert ph.prelaunch_ns(0) == [30, 40]
    assert ph.prelaunch_ns(1) == [31, 41]
    assert ph.clock_offsets() == [[pytest.approx(-6e-6), pytest.approx(5e-6)],
                                  [pytest.approx(-6e-6), pytest.approx(2e-6)]]
    run = _run(prelaunch_trace())
    assert _read(run, "prelaunch_ms.bcast") == pytest.approx(36.75e-6)


def test_prelaunch_takes_out_a_known_clock_offset():
    """Every device time of a trace read 4 late: the raw gaps grow by 4,
    the offset interval moves by 4, and the corrected gap stays."""
    base = prelaunch_trace()
    late = Trace([{k: [(n, s + 4, d) for n, s, d in v]
                   for k, v in dev.items()} for dev in base.devices],
                 base.host, base.window_ns)
    assert phases.Phases(late).prelaunch_ns(0) == [34, 44]
    assert phases.Phases(late).prelaunch_ms() == pytest.approx(
        phases.Phases(base).prelaunch_ms())


def offset_trace():
    """Chip 0 makes the payload (10-40 inside ``bench.payload``, 0-50) and
    runs the program 70-190 after the request at 60; chip 1's run is
    traced from 150 only, so the host bounds it loosely. In the one
    permute, chip 0 starts at 160 and is done at 165, chip 1 starts at 158
    and is done at 163."""
    host = [("bench.payload", 0, 50), ("bench.request", 60, 2),
            ("bench.wait", 62, 138)]
    chip0 = {"modules": [("jit_bench_payload_fn(1)", 10, 30),
                         ("jit_run(2)", 70, 120)],
             "ops": [("collective-permute-start", 160, 1),
                     ("collective-permute-done", 161, 4)]}
    chip1 = {"modules": [("jit_run(2)", 150, 45)],
             "ops": [("collective-permute-start", 158, 1),
                     ("collective-permute-done", 159, 4)]}
    return Trace([chip0, chip1], host, 200)


def test_clock_offsets_from_causal_pairs():
    """Alone, chip 0 reads [-10, 10] (its payload program starts 10 after
    its span starts and ends 10 before it ends) and chip 1 [-5, 90]. Chip
    1's permute is done 3 after chip 0's started: chip 1 reads at most 3
    more than chip 0, which caps it at 10 + 3 and lifts chip 0 to -5 - 3.
    """
    ph = phases.Phases(offset_trace(), PROGRAM)
    assert ph.clock_offsets() == [[pytest.approx(-8e-6), pytest.approx(10e-6)],
                                  [pytest.approx(-5e-6), pytest.approx(13e-6)]]
    alone = phases.Phases(offset_trace())
    assert alone.clock_offsets()[1] == [pytest.approx(-5e-6),
                                        pytest.approx(90e-6)]


# -- the traces recorded on a TPU v5e ---------------------------------------

# What every reader that predates the phases read on the recorded traces.
BEFORE = {
    "trace_bcast.json.gz": {
        "device_idle_share.bcast": 25.18058562158473,
        "ppermute_ms": 6.024259,
        "ppermute_roofline": 22.18398644546989,
        "local_ms.bcast": 39.753235749999995,
        "bcast_ici_peak_share": 2.2225970990805277},
    "trace_node.json.gz": {
        "device_idle_share.node": 8.14253629698526,
        "round_step_ms.node": 25.435969,
        "round_step_roofline": 2.8313293036174247},
}


def _recorded(name):
    path = DATA / name
    meta = json.loads(path.with_suffix("").with_suffix(".meta.json")
                      .read_text())
    tr = Trace.load(str(path))
    reqs = [(a, b, meta["info"]["message_bytes"]) for a, b in tr.requests()]
    return bench.Run(cell=bench.load_cell(meta["workload"]), peaks=PEAKS,
                     setup={}, requests=reqs, window=(0.0, tr.window_s),
                     info=meta["info"], trace=tr)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_readers_from_before_read_the_same(name):
    run = _recorded(name)
    assert {m: _read(run, m) for m in BEFORE[name]} == BEFORE[name]


@pytest.fixture(scope="module")
def program():
    """The broadcast program compiled for a described v5e 2x2, as the
    readers describe it on a host without a TPU."""
    try:
        return phases._program_of(bench.load_cell("bcast2x2_ddp_250m"))
    except RuntimeError as e:       # no TPU compiler in this jax build
        pytest.skip(f"no v5e 2x2 can be described here: {e}")


def test_phases_of_the_trace_recorded_before_the_scopes(program):
    """The program before the scopes compiled to the same instructions, so
    the scopes lay onto its trace: 3 broadcasts on 4 chips."""
    run = _recorded("trace_bcast.json.gz")
    run._phases = phases.Phases(run.trace, program)
    got = {m: _read(run, m) for m in NEW}
    assert got == pytest.approx({"place_ms.bcast": 0.797295,
                                 "step_ms.bcast": 19.69898125,
                                 "unstack_ms.bcast": 0.83606425,
                                 "other_ms.bcast": 18.42089525,
                                 "prelaunch_ms.bcast": 12.2038072083})
    assert sum(got[m] for m in PHASES) == pytest.approx(
        _read(run, "local_ms.bcast"), rel=1e-4)
    offsets = phases.of(run).clock_offsets()
    assert all(lo < 0 < hi and hi - lo < 2 for lo, hi in offsets)


def test_phases_of_a_trace_recorded_with_the_scopes(program):
    """3 broadcasts traced on a v5e 2x2 with the scopes in the program: the
    four phases partition ``local_ms.bcast``, and every chip's clock is
    bounded to within 2 ms of the host's, 0 inside."""
    run = _recorded("trace_bcast_phases.json.gz")
    run._phases = phases.Phases(run.trace, program)
    got = {m: _read(run, m) for m in NEW}
    assert all(v > 0 for v in got.values()), got
    local = _read(run, "local_ms.bcast")
    assert sum(got[m] for m in PHASES) == pytest.approx(local, rel=5e-3)
    assert got["step_ms.bcast"] > got["other_ms.bcast"] > 10 * max(
        got["place_ms.bcast"], got["unstack_ms.bcast"])
    offsets = phases.of(run).clock_offsets()
    assert len(offsets) == 4
    assert all(lo < 0 < hi and hi - lo < 2 for lo, hi in offsets)
