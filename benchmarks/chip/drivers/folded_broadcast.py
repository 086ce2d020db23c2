"""Driver for a broadcast over a fabric with more nodes than chips: the
fabric's nodes folded onto the cell's chips in contiguous blocks
(``ExecutablePlan.mesh(devices)``), one broadcast at a time.

Each request makes a fresh payload on the root chip from the seed
(``bench.payload``), calls the program's ``run`` with it (``bench.request``)
and waits until every node's copy is ready (``bench.wait``). The latency is
``bench.request`` plus ``bench.wait``.

Correctness: a sample of the window's requests, drawn from the seed, keeps
its output. After the window each sampled payload is made again from the
seed, and every fabric node's copy (all of them, on the chips that hold
them) is compared with it on raw bits (the reference of a broadcast is the
root's payload itself).

``info`` carries what the fold moves in one broadcast
(``FoldedSchedule.counters``), the row's bytes, the bytes a chip-level
permute carries and the bytes no implementation of the rows' moves can
avoid, for the per-layer readers.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding, PartitionSpec as P

import common
import folded
from repro.device import fold_schedule


class Cell:
    def __init__(self, config, traffic, devices, control=False):
        self.config, self.traffic = config, traffic
        self.devices = list(devices)
        self.control = control
        self.nbytes = int(traffic["message_bytes"])
        self.root = int(traffic["root"])
        self.dtype = jnp.dtype(config["dtype"])
        self.words = self.nbytes // self.dtype.itemsize
        self.info = {"message_bytes": self.nbytes}
        common.closed_loop(traffic)

    def setup(self) -> dict:
        model, times = folded.build_model(self.config, self.root)
        t0 = time.perf_counter()
        ex = model.executable(self.root, self.nbytes,
                              algo=self.config["algo"])
        times["schedule_s"] = time.perf_counter() - t0
        self.ex = ex
        self.mesh = ex.mesh(self.devices)
        chips = len(self.devices)
        coords = ([d.coords for d in self.devices]
                  if self.devices[0].platform == "tpu" else None)
        s = ex.schedule
        rows = ex.num_groups * s.K
        row = folded.row_bytes(self.words, rows, self.dtype.itemsize)
        count = fold_schedule(s, chips).counters(ex.num_groups, coords)
        self.nodes = model.topo.num_nodes
        self.info.update(
            candidate=ex.candidate, nodes=self.nodes, chips=chips,
            rows=rows + s.num_relay, row_bytes=row,
            permutes_per_request=count["permutes"],
            rows_local=count["rows_local"], rows_cross=count["rows_cross"],
            far_pairs=count.get("far_pairs"),
            bytes_per_permute=(row * count["permute_rows"]
                               / count["permutes"]
                               if count["permutes"] else None),
            least_bytes_per_chip=row * sum(
                a + b for a, b in zip(count["rows_sent"],
                                      count["rows_received"])) / chips,
            predicted_s=ex.predicted_time)

        self._payload = common.payload_fn(self.words, self.dtype)
        replicated = NamedSharding(self.mesh, P())
        self._payload_everywhere = jax.jit(
            common.bench_payload, static_argnums=(2, 3),
            out_shardings=replicated)
        self._run = self._control_run if self.control else ex.run

        # the first call of each program compiles (or loads from the cache)
        key = common.base_key(0)
        t0 = time.perf_counter()
        x = jax.block_until_ready(self._payload(key, 0))
        jax.block_until_ready(self._run(x, self.mesh))
        times["compile_s"] = time.perf_counter() - t0
        # one more request, warm, so that the window starts steady
        x = jax.block_until_ready(self._payload(key, 1))
        jax.block_until_ready(self._run(x, self.mesh))
        return times

    def _control_run(self, x, mesh):
        """The reference in the program's place, in the precision below the
        configuration's: the payload through bfloat16, in every node."""
        fn = self.__dict__.get("_control_fn")
        if fn is None:
            n = self.nodes
            fn = self._control_fn = jax.jit(
                lambda x: jnp.broadcast_to(common.to_bfloat16(x)[None],
                                           (n,) + x.shape),
                out_shardings=NamedSharding(mesh, P(mesh.axis_names[0])))
        return fn(x)

    def begin(self, seed: int) -> None:
        self.key = common.base_key(seed)
        self.sample = common.Reservoir(seed, int(self.traffic["check_sample"]))

    def request(self, i: int):
        with span("bench.payload"):
            x = jax.block_until_ready(self._payload(
                self.key, common.payload_index(self.traffic, i)))
        t0 = time.perf_counter()
        with span("bench.request"):
            out = self._run(x, self.mesh)
        with span("bench.wait"):
            out = jax.block_until_ready(out)
        t1 = time.perf_counter()
        self.sample.offer(i, out)
        return t0, t1, self.nbytes

    def check(self):
        """Every node's copy against the payload, bit for bit, for each
        sampled request."""
        mismatch = jax.jit(common.bench_mismatch_rows)
        words, nodes_short, failed = 0, set(), 0
        kept = self.sample.items()
        for _, i, out in kept:
            if out.shape[0] != self.nodes:
                raise ValueError(f"the program returned {out.shape[0]} "
                                 f"copies; the fabric has {self.nodes} nodes")
            ref = self._payload_everywhere(
                self.key, common.payload_index(self.traffic, i), self.words,
                self.dtype)
            bad = np.asarray(mismatch(out, ref))
            words += int(bad.sum())
            failed += int(bad.any())
            nodes_short |= {int(v) for v in np.nonzero(bad)[0]}
        checks = {"mismatched_words": [words, "<=", 0],
                  "nodes_short": [len(nodes_short), "<=", 0],
                  "requests_checked": [len(kept), ">=", 1]}
        return checks, self.sample.seen, failed
