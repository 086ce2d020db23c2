"""Driver for a broadcast across chips: ``ExecutablePlan.run`` from the
root chip to every chip of the fabric, one broadcast at a time.

Each request makes a fresh payload on the root chip from the seed
(``bench.payload``), calls the program's ``run`` with it (``bench.request``)
and waits until every chip's copy is ready (``bench.wait``). The latency is
``bench.request`` plus ``bench.wait``.

Correctness: a sample of the window's requests, drawn from the seed, keeps
its output. After the window each sampled payload is made again from the
seed, and every chip's copy is compared with it on raw bits, on the chips
(the reference of a broadcast is the root's payload itself).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding, PartitionSpec as P

import common


class Cell:
    def __init__(self, config, traffic, devices, control=False):
        self.config, self.traffic = config, traffic
        self.devices = devices
        self.control = control
        self.nbytes = int(traffic["message_bytes"])
        self.root = int(traffic["root"])
        self.dtype = jnp.dtype(config["dtype"])
        self.words = self.nbytes // self.dtype.itemsize
        self.info = {"message_bytes": self.nbytes}
        common.closed_loop(traffic)

    def setup(self) -> dict:
        from repro import device

        model, times = common.build_model(self.config, self.root)
        t0 = time.perf_counter()
        ex = model.executable(self.root, self.nbytes,
                              algo=self.config["algo"])
        times["schedule_s"] = time.perf_counter() - t0
        self.ex = ex
        self.mesh = ex.mesh()
        if list(self.mesh.devices.flat) != list(self.devices):
            raise RuntimeError("node i must run on jax.devices()[i]")
        if self.devices[0].platform == "tpu":
            device.node_coords(model.topo, list(self.mesh.devices.flat))
        s = ex.schedule
        rows = ex.num_groups * s.K
        plen = -(-self.words // rows)
        self.info.update(
            candidate=ex.candidate, rows=rows + s.num_relay,
            row_bytes=plen * self.dtype.itemsize,
            permutes_per_request=s.d * s.num_cycles(ex.num_groups),
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
        configuration's: the payload through bfloat16, on every chip."""
        fn = self.__dict__.get("_control_fn")
        if fn is None:
            n = mesh.devices.size
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
        """Every chip's copy against the payload, bit for bit, for each
        sampled request."""
        mismatch = jax.jit(common.bench_mismatch_rows)
        words, chips_short, failed = 0, set(), 0
        kept = self.sample.items()
        for _, i, out in kept:
            ref = self._payload_everywhere(
                self.key, common.payload_index(self.traffic, i), self.words,
                self.dtype)
            bad = np.asarray(mismatch(out, ref))
            words += int(bad.sum())
            failed += int(bad.any())
            chips_short |= {int(v) for v in np.nonzero(bad)[0]}
        checks = {"mismatched_words": [words, "<=", 0],
                  "chips_short": [len(chips_short), "<=", 0],
                  "requests_checked": [len(kept), ">=", 1]}
        return checks, self.sample.seen, failed
