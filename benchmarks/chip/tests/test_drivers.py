"""Both cells end to end at a tiny size on four CPU devices: sound runs come
out correct; the control, and each fault the cell can have planted in the
timed path, come out not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import reference
from repro.device import pallas_step, runner

TINY = {"message_bytes": 1 << 16}
SEED = 2 ** 33 + 5          # wider than 32 bits, as the driver's seeds are
CELLS = ["bcast2x2_ddp_250m", "node_ddp_250m"]


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
@pytest.mark.parametrize("seed", [SEED, 11])
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


def _plant(monkeypatch, step):
    monkeypatch.setattr(pallas_step, "round_step", step)
    monkeypatch.setattr(runner, "round_step", step)


def unchanged_state(buf, rec, r_idx, r_ok, s_idx, s_ok, **kw):
    """A step that returns its state unchanged (it still sends)."""
    _, val = pallas_step.round_step_ref(buf, rec, r_idx, r_ok, s_idx, s_ok)
    return buf, val


def half_left_out(buf, rec, r_idx, r_ok, s_idx, s_ok, **kw):
    """The second half of the rows is never written."""
    r_ok = jnp.logical_and(r_ok, r_idx < buf.shape[0] // 2)
    return pallas_step.round_step_ref(buf, rec, r_idx, r_ok, s_idx, s_ok)


def answer_altered(buf, rec, r_idx, r_ok, s_idx, s_ok, **kw):
    """One bit of every written row flipped where it is written."""
    bits = jax.lax.bitcast_convert_type(rec, jnp.uint32)
    rec = jax.lax.bitcast_convert_type(bits.at[0].set(bits[0] ^ 1),
                                       rec.dtype)
    return pallas_step.round_step_ref(buf, rec, r_idx, r_ok, s_idx, s_ok)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged_state, half_left_out,
                                   answer_altered])
def test_fault_in_the_step_is_not_correct(monkeypatch, cell, fault):
    _plant(monkeypatch, fault)
    assert not run(cell)["correct"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    """Each chip keeps what it would have sent instead of receiving."""
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, axis, perm: x)
    assert not run("bcast2x2_ddp_250m")["correct"]


def test_node_replay_by_hand():
    buf0 = np.zeros((3, 2), np.float32)
    packets = np.arange(6, dtype=np.float32).reshape(3, 2)
    steps = np.array([[0, 0, 0, 0, 0],      # nothing received or sent
                      [1, 1, 1, 1, 1],      # receive packet 1, send it
                      [2, 1, 0, 0, 2]])     # receive packet 2
    buf, acc = reference.node_replay(buf0, packets, steps)
    assert buf.tolist() == [[0, 0], [2, 3], [4, 5]]
    sent = packets[1].view(np.uint32)
    assert acc.tolist() == reference.rotxor(sent, np.uint32(0)).tolist()
    assert reference.rotxor(np.uint32(0x80000000), np.uint32(0)) == 1
    assert reference.words_differing(buf, buf0) == 4
