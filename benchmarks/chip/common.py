"""Pieces the drivers share: the fabric from a configuration file, payloads
made on the chip from the seed, and the seeded sample of requests that the
correctness check reads after the window.

The benchmark's own jitted programs are named ``bench_*``, so that the trace
reduction tells them from the system under test.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def build_model(config: dict, root: int):
    """``repro.api.compile`` of the configuration's fabric with an
    in-memory ``PlanServer``, and the plan for ``root`` fetched once.
    Returns the model and ``{"plan_s": seconds}``."""
    from repro import api, device  # noqa: F401  (imported before timing)
    from repro.core import topology as T

    fab = config["fabric"]
    topo = getattr(T, fab["constructor"])(*fab["args"], preset=fab["preset"])
    model = api.compile(topo, fab["mode"], server=True)
    t0 = time.perf_counter()
    model.plan(root)
    return model, {"plan_s": time.perf_counter() - t0}


def closed_loop(traffic: dict) -> None:
    """The drivers run one request at a time, back to back; a mix that asks
    for anything else is refused, not run as something it is not."""
    if traffic.get("loop") != "closed" or traffic.get("outstanding") != 1:
        raise ValueError(f"unsupported traffic: loop={traffic.get('loop')!r} "
                         f"outstanding={traffic.get('outstanding')!r}; the "
                         f"drivers run a closed loop with one outstanding")


def payload_index(traffic: dict, i: int) -> int:
    """Which payload request ``i`` carries: its own with ``fresh_payload``,
    else the seed's first."""
    return i if traffic["fresh_payload"] else 0


def base_key(seed: int) -> np.ndarray:
    """Raw threefry key data from any whole-number seed (wider than 32
    bits too)."""
    return np.random.SeedSequence(seed % (1 << 64)).generate_state(
        2, dtype=np.uint32)


def bench_payload(key_data, i, words: int, dtype):
    """Request ``i``'s payload: ``words`` values in [0, 1) of ``dtype``."""
    key = jax.random.fold_in(jax.random.wrap_key_data(key_data), i)
    return jax.random.uniform(key, (words,), dtype)


def payload_fn(words: int, dtype):
    """The payload program for one size, jitted: ``fn(key_data, i)``."""
    def bench_payload_fn(key_data, i):
        return bench_payload(key_data, i, words, dtype)
    return jax.jit(bench_payload_fn)


def to_bfloat16(x):
    """``x`` (float32) rounded to the nearest bfloat16, ties to even, and
    widened back. Done on the bits with integer operations: XLA on the TPU
    removes a float32 -> bfloat16 -> float32 pair of converts as excess
    precision, so ``x.astype(bfloat16).astype(float32)`` can be ``x``."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, x.dtype)


def bench_mismatch_rows(out, ref):
    """Words of each row of ``out`` whose bits differ from ``ref``'s."""
    a = jax.lax.bitcast_convert_type(out, jnp.uint32)
    b = jax.lax.bitcast_convert_type(ref, jnp.uint32)
    return jnp.sum(a != b[None], axis=tuple(range(1, a.ndim)),
                   dtype=jnp.int32)


class Reservoir:
    """A uniform sample of ``size`` requests per group, drawn from the seed
    (reservoir sampling), holding what each sampled request returned.

    Requests are grouped by ``group``: the broadcast keeps one group, the
    node program one per node, so that every node is checked."""

    def __init__(self, seed: int, size: int):
        self.rng = np.random.default_rng([seed % (1 << 64), 1])
        self.size = size
        self.groups = {}
        self.seen = 0

    def offer(self, i: int, value, group=0) -> None:
        self.seen += 1
        g = self.groups.setdefault(group, {"n": 0, "kept": []})
        g["n"] += 1
        if len(g["kept"]) < self.size:
            g["kept"].append((i, value))
        else:
            j = int(self.rng.integers(g["n"]))
            if j < self.size:
                g["kept"][j] = (i, value)

    def items(self):
        return [(group, i, v) for group, g in sorted(self.groups.items())
                for i, v in g["kept"]]
