#!/usr/bin/env python3
"""Bring-up smoke run of the broadcast path on TPU v5e chips.

Runs the size-ladder broadcast deployment (ROADMAP W1): ``torus2d(2, 2)``
with ``tpu_ici`` links, roots 0 and 3, and a float32 payload made from
``--seed``, through the entry points a user calls
(``repro.api.compile(...).executable(root, nbytes)``, ``.verify``,
``.measure``). Every result is checked bit for bit. Plans come from an
in-memory ``PlanServer``, never from files on disk.

    python3 chip_smoke.py             # one chip: phases a-d
    python3 chip_smoke.py --chips 4   # a 2x2 host: the broadcast only

The broadcast between chips runs with ``--chips 4``; the default run covers
what one chip holds:

  a. the device; exits non-zero unless JAX's platform is ``tpu``;
  b. for each ladder size and root, the ``ExecutablePlan``: candidate, K,
     d, m, relay rows, plan-fetch and schedule time;
  c. the packed round step over that plan's packet buffer (``m*K + relay``
     rows), driven for every cycle with each fabric node's send/recv
     tables, root first: the jnp step at every size, and the Pallas kernel
     compiled by Mosaic wherever ``check_kernel_limits`` takes the buffer
     (its ``ValueError`` everywhere else). Both are compared bit for bit
     with a numpy replay of the same scatters and gathers;
  d. ``KernelSim.run_lowered(jit=True)`` on the un-foldable binomial list of
     ``mesh2d(16, 16)`` at 64e6 bytes, compared exactly with
     ``CompiledSim``.

``--chips 4`` runs BBS, binomial and chain broadcasts from both roots at
every ladder size (BBS also with the Pallas round step, wherever the
kernel takes the buffer), checks that all four chips hold the root's bytes,
prints ``measure()`` beside the simulator's ``predicted_time``, and checks
that every fabric edge joins neighbouring chips.

Times printed are smoke timings of one run, not benchmark numbers. The last
line of standard output is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LADDER = (1 << 10, 1 << 20, 64 << 20, 1 << 30)      # bytes
ROOTS = (0, 3)
log = functools.partial(print, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


def device_report(chips: int) -> dict:
    """Phase a: the device as JAX reports it; no TPU is an error."""
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's platform is {dev['platform']}")
    if dev["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} chips; JAX sees "
                         f"{dev['count']}")
    return dev


def payload(nbytes: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).random(nbytes // 4, dtype=np.float32)


def build(model, root: int, nbytes: int, algo: str = "bbs", config=None):
    """Phase b: plan fetch + schedule compile for one (root, size)."""
    from repro.device.runner import _pad_packets

    t0 = time.perf_counter()
    model.plan(root)
    t_plan = time.perf_counter() - t0
    t0 = time.perf_counter()
    ex = model.executable(root, nbytes, algo=algo, config=config)
    t_sched = time.perf_counter() - t0
    s = ex.schedule
    rows = ex.num_groups * s.K + s.num_relay
    row = jax.eval_shape(functools.partial(_pad_packets,
                                           num_packets=ex.num_groups * s.K),
                         jax.ShapeDtypeStruct((nbytes // 4,),
                                              np.float32)).shape[1:]
    step = "pallas" if ex.device.use_pallas else "jnp"
    log(f"plan: algo={algo} step={step} root={root} nbytes={nbytes} "
        f"candidate={ex.candidate} K={s.K} d={s.d} m={ex.num_groups} "
        f"relay={s.num_relay} buffer={'x'.join(map(str, (rows,) + row))} "
        f"plan_fetch_s={t_plan!r} schedule_s={t_sched!r}")
    return ex


# -- phase c: the packed round step on one chip ------------------------------

def step_table(ex, node: int) -> np.ndarray:
    """Every round-step call the runner makes on ``node``, in order: rows
    of (recv_ix, recv_ok, send_ix, send_ok, src), where ``src`` is the
    packet row fed in as the received row (one chip has no ppermute).

    The indexing follows ``DeviceSchedule``'s contract: packet index
    ``c*K + rel`` masked outside ``[0, m*K)``, relay rows absolute after
    the packet rows."""
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
    src = (np.arange(len(tab)) % total).astype(np.int32)
    return np.column_stack([tab, src])


def _rotxor(acc, bits):
    return ((acc << 1) | (acc >> 31)) ^ bits


@functools.partial(jax.jit, static_argnames="use_pallas", donate_argnums=0)
def drive(buf, packets, steps, use_pallas):
    """Run the round steps of ``steps`` over ``buf``; returns the final
    buffer and a rotate-xor digest of every gathered row's bits."""
    from repro.device.pallas_step import round_step

    def body(carry, st):
        buf, acc = carry
        rec = jax.lax.dynamic_index_in_dim(packets, st[4], keepdims=False)
        buf, val = round_step(buf, rec, st[0], st[1] != 0, st[2], st[3] != 0,
                              use_pallas=use_pallas)
        bits = jax.lax.bitcast_convert_type(val, jnp.uint32)
        return (buf, _rotxor(acc, bits)), None

    acc = jnp.zeros(buf.shape[1:], jnp.uint32)
    (buf, acc), _ = jax.lax.scan(body, (buf, acc), steps)
    return buf, acc


def replay(buf0: np.ndarray, packets: np.ndarray, steps: np.ndarray):
    """The numpy reference of ``drive``."""
    buf = buf0.copy()
    acc = np.zeros(buf.shape[1:], np.uint32)
    zero = np.zeros(buf.shape[1:], buf.dtype)
    for r_ix, r_ok, s_ix, s_ok, src in steps.tolist():
        if r_ok:
            buf[r_ix] = packets[src]
        val = buf[s_ix] if s_ok else zero
        acc = _rotxor(acc, val.view(np.uint32))
    return buf, acc


def round_step_phase(ex, x: np.ndarray, platform: str, stats: dict) -> None:
    """Phase c for one plan: every node's tables, jnp and Pallas steps,
    over the packet buffer ``bbs_broadcast`` builds (rows of whole
    tiles, then the relay rows)."""
    from repro.device.runner import _pad_packets

    s = ex.schedule
    total = ex.num_groups * s.K
    packets = np.asarray(_pad_packets(jnp.asarray(x), total))
    buf0 = np.concatenate([packets, np.zeros((s.num_relay,)
                                             + packets.shape[1:],
                                             np.float32)])
    packets_d = jax.device_put(packets)
    nodes = [ex.root] + [v for v in range(s.num_devices) if v != ex.root]
    tables = {v: step_table(ex, v) for v in nodes}
    programs = {}
    for use_pallas in (False, True):
        t0 = time.perf_counter()
        try:
            programs[use_pallas] = drive.lower(
                jax.ShapeDtypeStruct(buf0.shape, buf0.dtype), packets_d,
                jax.ShapeDtypeStruct(tables[ex.root].shape, np.int32),
                use_pallas).compile()
        except ValueError as e:     # the kernel's own limit, named
            check(use_pallas and "VMEM_LIMIT_BYTES" in str(e),
                  f"unexpected refusal: {e}")
            log(f"  pallas: refused, {e}")
            continue
        stats["compile_s"] += time.perf_counter() - t0
        stats["programs"] += 1
    if True in programs:
        check(("tpu_custom_call" in programs[True].as_text())
              == (platform == "tpu"),
              "Pallas kernel compiled by Mosaic on the TPU")
    for node in nodes:
        steps = tables[node]
        ref_buf, ref_acc = replay(buf0, packets, steps)
        steps_d = jnp.asarray(steps)
        for use_pallas in programs:
            buf_d = jax.block_until_ready(jnp.asarray(buf0))
            t0 = time.perf_counter()
            got_buf, got_acc = jax.block_until_ready(
                programs[use_pallas](buf_d, packets_d, steps_d))
            t_run = time.perf_counter() - t0
            same = (np.array_equal(np.asarray(got_buf).view(np.uint32),
                                   ref_buf.view(np.uint32))
                    and np.array_equal(np.asarray(got_acc), ref_acc))
            step = "pallas" if use_pallas else "jnp"
            log(f"  round_step {step} node={node} steps={len(steps)} "
                f"bit_exact={same} smoke_run_s={t_run!r}")
            check(same, f"{step} round step on node {node} vs numpy")


def kernel_engine_phase() -> None:
    """Phase d: the jitted event core against the numpy engine."""
    from repro.core import kernelsim as KS
    from repro.core import topology as T
    from repro.core.baselines import lower_baseline
    from repro.core.fastsim import CompiledSim
    from repro.core.intersection import FULL_DUPLEX, ConflictModel

    topo = T.mesh2d(16, 16)
    cm = ConflictModel(topo, FULL_DUPLEX)
    ctl = lower_baseline(topo, cm, "binomial", 0, 64e6)
    check(not (ctl.seg is not None and ctl.seg.foldable),
          "binomial list must be un-foldable")
    ref = CompiledSim(topo, cm, 0).run_lowered(ctl)
    t0 = time.perf_counter()
    got = KS.KernelSim(topo, cm, 0).run_lowered(ctl, jit=True)
    t_run = time.perf_counter() - t0
    same = (got.finish_time == ref.finish_time
            and got.deliveries == ref.deliveries
            and got.node_finish == ref.node_finish
            and got.group_finish == ref.group_finish)
    log(f"kernelsim: binomial mesh2d_16x16 64e6 tasks={ctl.n} "
        f"core_device={KS._core_device().platform} identical={same} "
        f"finish_time={got.finish_time!r} smoke_run_s={t_run!r}")
    check(same, "KernelSim jit core vs CompiledSim")


def one_chip(model, sizes, seed: int, platform: str) -> None:
    stats = {"compile_s": 0.0, "programs": 0}
    for nbytes in sizes:
        x = payload(nbytes, seed)
        for root in ROOTS:
            ex = build(model, root, nbytes)
            round_step_phase(ex, x, platform, stats)
    kernel_engine_phase()
    log(f"compile: {stats['programs']} round-step programs in "
        f"{stats['compile_s']!r} s")


# -- --chips 4: the broadcast across chips -----------------------------------

def four_chips(model, sizes, seed: int) -> None:
    from repro import device
    from repro.core.simconfig import DeviceConfig, SimConfig

    pallas = SimConfig(device=DeviceConfig(use_pallas=True))
    mesh = None
    for nbytes in sizes:
        x = payload(nbytes, seed)
        for root in ROOTS:
            for algo, config in (("bbs", None), ("binomial", None),
                                 ("bbs", pallas)):
                ex = build(model, root, nbytes, algo, config)
                if mesh is None:
                    mesh = ex.mesh()
                    coords = device.node_coords(model.topo,
                                                list(mesh.devices.flat))
                    log(f"node -> chip coords: {coords} (every fabric edge "
                        f"on a link)")
                t0 = time.perf_counter()
                try:
                    chk = ex.verify(x, mesh)
                except ValueError as e:     # the kernel's own limit, named
                    check(config is pallas and "VMEM_LIMIT_BYTES" in str(e),
                          f"unexpected refusal: {e}")
                    log(f"  pallas: refused, {e}")
                    continue
                t_first = time.perf_counter() - t0
                check(chk.ok, f"{algo} root {root} {nbytes} B: chips "
                              f"{chk.missing} differ from the root's bytes")
                t = ex.measure(x, mesh, reps=2)
                name = algo + ("+pallas" if config is pallas else "")
                log(f"  broadcast {name} root={root} nbytes={nbytes} "
                    f"bit_exact_chips={len(chk.required)}/"
                    f"{len(chk.required)} first_call_s={t_first!r} "
                    f"smoke_measure_s={t!r} "
                    f"predicted_s={ex.predicted_time!r}")
            fn = jax.jit(functools.partial(device.chain_broadcast, mesh=mesh,
                                           axis=mesh.axis_names[0],
                                           root=root))
            out = np.asarray(fn(jnp.asarray(x)))
            bad = [v for v in range(out.shape[0])
                   if out[v].tobytes() != x.tobytes()]
            log(f"  broadcast chain root={root} nbytes={nbytes} "
                f"bit_exact_chips={out.shape[0] - len(bad)}/{out.shape[0]}")
            check(not bad, f"chain root {root} {nbytes} B: chips {bad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the broadcast across a 2x2 host")
    ap.add_argument("--seed", type=int, default=0, help="payload seed")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    dev = device_report(args.chips)

    from repro import api
    from repro.core import topology as T
    model = api.compile(T.torus2d(2, 2, preset="tpu_ici"), server=True)
    if args.chips == 4:
        four_chips(model, LADDER, args.seed)
    else:
        one_chip(model, LADDER, args.seed, dev["platform"])
    log(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
