"""Driver for one chip's share of a broadcast: the node program that
``repro.device.runner.bbs_broadcast`` runs on a fabric node, without the
exchange between chips.

The node program is every round step of every cycle, in the runner's order,
from the ``DeviceSchedule`` tables of ``executable(root, nbytes)``: the send
of sub-round 0, then for each sub-round the write of the received row and
the read of the next row to send. The row a node would receive is the
payload's packet at the index it writes, kept in HBM; nothing stands in for
the absent chips. Requests cycle through the traffic's ``nodes``, one
program each, with a fresh payload and a fresh packet buffer (the payload
on the root, zeros elsewhere, as the runner starts them).

This driver depends on ``round_step(buf, rec, r_idx, r_ok, s_idx, s_ok)``
of ``repro.device.pallas_step``, called with its defaults, and on the
``DeviceSchedule`` fields ``K``, ``d``, ``send_rel``, ``recv_rel``,
``send_abs``, ``recv_abs``, ``num_relay`` and ``num_cycles``.

Correctness: a sample of each node's requests, drawn from the seed, keeps
its final buffer and the digest of every row it sent. After the window the
packet rows of the buffer are compared with the payload (every node of a
broadcast ends holding every packet), and buffer and digest with the numpy
replay of the same steps (``reference.node_replay``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

import common
import reference


def step_table(ex, node: int) -> np.ndarray:
    """Every round step the runner makes on ``node``, in order: rows of
    (recv_ix, recv_ok, send_ix, send_ok, src). ``src`` is the packet row fed
    in as the received row: the row the node writes, where that is a packet
    row."""
    from repro.device.schedule import _NOSEND

    s = ex.schedule
    total = ex.num_groups * s.K

    def slot(table_rel, table_abs, r, c):
        rel, ab = int(table_rel[r, node]), int(table_abs[r, node])
        if ab >= 0:
            return total + ab, 1
        pk = c * s.K + rel
        if rel != _NOSEND and 0 <= pk < total:
            return pk, 1
        return min(max(pk, 0), total - 1), 0

    steps = []
    for c in range(s.num_cycles(ex.num_groups)):
        steps.append((0, 0) + slot(s.send_rel, s.send_abs, 0, c))
        for r in range(s.d):
            nxt = (slot(s.send_rel, s.send_abs, r + 1, c) if r + 1 < s.d
                   else (0, 0))
            steps.append(slot(s.recv_rel, s.recv_abs, r, c) + nxt)
    tab = np.asarray(steps, dtype=np.int32)
    src = np.where(tab[:, 1] != 0, np.minimum(tab[:, 0], total - 1), 0)
    return np.column_stack([tab, src.astype(np.int32)])


def _drive(buf, packets, steps):
    """The node program: the round steps of ``steps`` over ``buf``. Returns
    the final buffer and a rotate-xor digest of every row sent."""
    from repro.device import pallas_step

    def body(carry, st):
        buf, acc = carry
        rec = jax.lax.dynamic_index_in_dim(packets, st[4], keepdims=False)
        buf, val = pallas_step.round_step(buf, rec, st[0], st[1] != 0,
                                          st[2], st[3] != 0)
        bits = jax.lax.bitcast_convert_type(val, jnp.uint32)
        return (buf, reference.rotxor(acc, bits)), None

    acc = jnp.zeros(buf.shape[1:], jnp.uint32)
    (buf, acc), _ = jax.lax.scan(body, (buf, acc), steps)
    return buf, acc


def bench_node_packets(key_data, i, words, total, plen, dtype):
    """Payload ``i`` cut into ``total`` packet rows of ``plen`` values."""
    flat = common.bench_payload(key_data, i, words, dtype)
    return jnp.pad(flat, (0, total * plen - words)).reshape(total, plen)


def bench_node_buffer(packets, is_root, rows):
    """A fresh packet buffer: the packets on the root, zeros elsewhere, and
    zeroed relay rows after them."""
    buf = jnp.zeros((rows,) + packets.shape[1:], packets.dtype)
    return buf.at[:packets.shape[0]].set(
        jnp.where(is_root, packets, jnp.zeros_like(packets)))


class Cell:
    def __init__(self, config, traffic, devices, control=False):
        self.config, self.traffic = config, traffic
        self.devices = devices
        self.control = control
        self.nbytes = int(traffic["message_bytes"])
        self.root = int(traffic["root"])
        self.nodes = [int(v) for v in traffic["nodes"]]
        self.dtype = jnp.dtype(config["dtype"])
        self.words = self.nbytes // self.dtype.itemsize
        self.info = {"message_bytes": self.nbytes}
        common.closed_loop(traffic)

    def setup(self) -> dict:
        model, times = common.build_model(self.config, self.root)
        t0 = time.perf_counter()
        ex = model.executable(self.root, self.nbytes,
                              algo=self.config["algo"])
        times["schedule_s"] = time.perf_counter() - t0
        s = ex.schedule
        self.total = ex.num_groups * s.K
        self.rows = self.total + s.num_relay
        self.plen = -(-self.words // self.total)
        row_bytes = self.plen * self.dtype.itemsize
        self.tables = {v: step_table(ex, v) for v in self.nodes}
        # least HBM bytes of a node program: each received row read once
        # and written once, each sent row read once
        least = [row_bytes * (2 * int(t[:, 1].sum()) + int(t[:, 3].sum()))
                 for t in self.tables.values()]
        self.info.update(candidate=ex.candidate, rows=self.rows,
                         row_bytes=row_bytes,
                         steps_per_request=len(self.tables[self.nodes[0]]),
                         least_bytes_per_request=sum(least) / len(least))

        dev = self.devices[0]
        self._steps = {v: jax.device_put(t, dev)
                       for v, t in self.tables.items()}
        self._packets_fn = jax.jit(bench_node_packets,
                                   static_argnums=(2, 3, 4, 5))
        self._buffer_fn = jax.jit(bench_node_buffer, static_argnums=2)
        program = self._control if self.control else _drive
        self._drive = jax.jit(program, donate_argnums=0)

        # the first call of each program compiles (or loads from the cache);
        # one more request, warm, so that the window starts steady
        self.begin(0)
        t0 = time.perf_counter()
        self.request(0)
        times["compile_s"] = time.perf_counter() - t0
        self.request(1)
        return times

    def _packets(self, i):
        """Request ``i``'s packets, made once per payload."""
        k = common.payload_index(self.traffic, i)
        if k not in self._made:
            self._made = {k: jax.block_until_ready(self._packets_fn(
                self.key, k, self.words, self.total, self.plen,
                self.dtype))}
        return self._made[k]

    def _buffer(self, packets, node):
        return jax.block_until_ready(
            self._buffer_fn(packets, node == self.root, self.rows))

    @staticmethod
    def _control(buf, packets, steps):
        """The node program with every row it writes through bfloat16: the
        reference's precision below the configuration's."""
        return _drive(buf, common.to_bfloat16(packets), steps)

    def begin(self, seed: int) -> None:
        self.key = common.base_key(seed)
        self._made = {}
        if not self.traffic["fresh_payload"]:
            self._packets(0)            # the seed's payload, before the window
        self.sample = common.Reservoir(seed, int(self.traffic["check_sample"]))

    def request(self, i: int):
        node = self.nodes[i % len(self.nodes)]
        with span("bench.payload"):
            packets = self._packets(i)
            buf = self._buffer(packets, node)
        t0 = time.perf_counter()
        with span("bench.request"):
            out = self._drive(buf, packets, self._steps[node])
        with span("bench.wait"):
            out = jax.block_until_ready(out)
        t1 = time.perf_counter()
        self.sample.offer(i, out, group=node)
        return t0, t1, self.nbytes

    def check(self):
        """Final buffer against the payload and against the numpy replay;
        digest of the sent rows against the replay's."""
        off_payload = off_replay = failed = 0
        kept = self.sample.items()
        for node, i, (buf, acc) in kept:
            dev_packets = self._packets(i)
            buf0 = np.asarray(self._buffer(dev_packets, node))
            packets = np.asarray(dev_packets)
            ref_buf, ref_acc = reference.node_replay(buf0, packets,
                                                     self.tables[node])
            got_buf, got_acc = np.asarray(buf), np.asarray(acc)
            a = reference.words_differing(got_buf[:self.total], packets)
            b = (reference.words_differing(got_buf, ref_buf)
                 + reference.words_differing(got_acc, ref_acc))
            off_payload += a
            off_replay += b
            failed += int(a + b > 0)
        checks = {"words_off_payload": [off_payload, "<=", 0],
                  "words_off_replay": [off_replay, "<=", 0],
                  "nodes_checked": [len({k[0] for k in kept}), ">=",
                                    len(set(self.nodes))]}
        return checks, self.sample.seen, failed
