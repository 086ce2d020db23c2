"""Execute device schedules as ``lax.ppermute`` programs under shard_map.

The cycle loop is a ``lax.scan`` (compile size independent of message size);
the d sub-rounds within a cycle are unrolled (d is small: 1-8 for the BBS
families). Each sub-round is a matching => exactly one XLA
``collective-permute``; between permutes every device runs the packed
scatter+gather step (``repro.device.pallas_step``). This is the TPU-native
rendering of the paper's algorithm: every ICI link carries a packet every
round — balanced saturation.

``device_mesh`` builds the execution mesh from whatever devices the process
has: the chips of a TPU host, or on the CPU emulated host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` **set before jax
initializes** — the device count cannot change afterwards, so tests spawn
a subprocess, see tests/test_device.py and docs/device.md).
``node_coords`` checks that every fabric edge lands on a chip-to-chip
link.

The three runners name their phases with ``jax.named_scope``, so that a
profiler trace or the compiled HLO (``op_name`` metadata) tells them apart:
``bcast.place`` (padding into packet rows, relay rows, zeroing the non-root
copies), ``bcast.cycle`` (the scan), ``bcast.step`` (the round step, set in
``repro.device.pallas_step.round_step``) and ``bcast.unstack`` (the
stacked output cut back to the payload); a collective permute is found by
its opcode. Scopes are metadata only: the optimized program is the same.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.device.pallas_step import LANES, round_step, sublanes
from repro.device.schedule import _NOSEND, DeviceSchedule


def device_mesh(num_devices: int, axis: str = "dev") -> Mesh:
    """A 1-D mesh over the first ``num_devices`` process devices, one per
    fabric node, in ``jax.devices()`` order."""
    devs = jax.devices()
    if len(devs) < num_devices:
        platform = devs[0].platform
        hint = (f"; emulate host devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={num_devices} set "
                f"before jax initializes" if platform == "cpu" else "")
        raise RuntimeError(
            f"the fabric needs {num_devices} devices, one per node; the "
            f"{platform} platform has {len(devs)}{hint}")
    return Mesh(np.array(devs[:num_devices]), (axis,))


def node_coords(topo, devices) -> Dict[int, Tuple[int, ...]]:
    """Physical chip coordinates of each fabric node (node ``i`` runs on
    ``devices[i]``, the ``device_mesh`` order).

    Raises ``ValueError`` when a fabric edge joins two chips that are not
    neighbours (coordinates one step apart on one axis): that edge's
    ppermute would cross an intermediate chip rather than one link. On a
    v5e 2x2 host, ``jax.devices()`` enumerates (0,0), (1,0), (0,1), (1,1),
    which puts every edge of ``torus2d(2, 2)`` on a link and two of the
    four edges of ``ring(4)`` on diagonals."""
    coords = {v: tuple(int(c) for c in devices[v].coords)
              for v in range(topo.num_nodes)}
    for u, v in topo.candidate_edges:
        step = sum(abs(a - b) for a, b in zip(coords[u], coords[v]))
        if step != 1:
            raise ValueError(
                f"fabric edge ({u}, {v}) of {topo.name} joins chips at "
                f"{coords[u]} and {coords[v]}, which share no link")
    return coords


def _pad_packets(x: jax.Array, num_packets: int) -> jax.Array:
    """The payload cut into ``num_packets`` rows, zero-padded at the end.

    A TPU lays an array out in tiles of ``sublanes(itemsize)`` x ``LANES``
    (4 KiB) over its last two dimensions. Rows of a 2-D ``(rows, plen)``
    buffer would share their tiles, 8 rows to a tile for 32-bit dtypes, so
    a row access would move the tiles of 8 rows, and the flat payload
    would have to be interleaved into them. Each row is therefore rounded
    up to whole tiles and shaped ``(plen / LANES, LANES)``: a row is its
    own tiles, and padding the flat payload into rows and taking them back
    out are bitcasts."""
    flat = x.reshape(-1)
    tile = sublanes(flat.dtype.itemsize) * LANES
    plen = -(-flat.size // (num_packets * tile)) * tile
    flat = jnp.pad(flat, (0, plen * num_packets - flat.size))
    return flat.reshape(num_packets, plen // LANES, LANES)


def _unstack(out: jax.Array, x: jax.Array) -> jax.Array:
    """The stacked ``(n, rows, *row)`` buffers cut back to ``(n,) +
    x.shape``: the payload is the leading ``x.size`` words of each."""
    n = out.shape[0]
    return out.reshape(n, -1)[:, :x.size].reshape((n,) + x.shape)


def bbs_broadcast(x: jax.Array, mesh: Mesh, axis: str, sched: DeviceSchedule,
                  num_groups: int, *, use_pallas: bool = False) -> jax.Array:
    """Broadcast `x` from the schedule's root device to every device along
    `axis`. Returns the per-device copies stacked on a leading axis (callers
    that need the replicated value take [i] on their own shard).

    The input is only read on the root device; other devices' values are
    ignored (zeroed before the pipeline runs). Relay rows (multi-hop plan
    edges) live after the ``m*K`` packet rows and are dropped on return.
    """
    n = mesh.shape[axis]
    assert n == sched.num_devices
    m = num_groups
    K = sched.K
    total = m * K
    with jax.named_scope("bcast.place"):
        packets = _pad_packets(x, total)
        if sched.num_relay:
            packets = jnp.concatenate(
                [packets, jnp.zeros((sched.num_relay,) + packets.shape[1:],
                                    packets.dtype)])
    send_rel = jnp.asarray(sched.send_rel)
    recv_rel = jnp.asarray(sched.recv_rel)
    send_abs = jnp.asarray(sched.send_abs)
    recv_abs = jnp.asarray(sched.recv_abs)
    perms = sched.perms
    num_cycles = sched.num_cycles(m)

    def body(buf_x):
        idx = jax.lax.axis_index(axis)
        with jax.named_scope("bcast.place"):
            buf = jnp.where(idx == sched.root, buf_x, jnp.zeros_like(buf_x))

        def slot(r, c):
            """(send_idx, send_ok, recv_idx, recv_ok) for sub-round r."""
            s_rel, s_abs = send_rel[r, idx], send_abs[r, idx]
            r_rel, r_abs = recv_rel[r, idx], recv_abs[r, idx]
            s_pk, r_pk = c * K + s_rel, c * K + r_rel
            s_ok = (s_abs >= 0) | ((s_rel != _NOSEND)
                                   & (s_pk >= 0) & (s_pk < total))
            r_ok = (r_abs >= 0) | ((r_rel != _NOSEND)
                                   & (r_pk >= 0) & (r_pk < total))
            s_ix = jnp.where(s_abs >= 0, total + s_abs,
                             jnp.clip(s_pk, 0, total - 1))
            r_ix = jnp.where(r_abs >= 0, total + r_abs,
                             jnp.clip(r_pk, 0, total - 1))
            return s_ix, s_ok, r_ix, r_ok

        def cycle(buf, c):
            s_ix, s_ok, _, _ = slot(0, c)
            zero = jnp.zeros(buf.shape[1:], buf.dtype)
            buf, val = round_step(buf, zero, 0, False, s_ix, s_ok,
                                  use_pallas=use_pallas)
            for r in range(sched.d):
                rec = jax.lax.ppermute(val, axis, perms[r])
                _, _, r_ix, r_ok = slot(r, c)
                if r + 1 < sched.d:
                    ns_ix, ns_ok, _, _ = slot(r + 1, c)
                else:
                    ns_ix, ns_ok = 0, jnp.bool_(False)
                buf, val = round_step(buf, rec, r_ix, r_ok, ns_ix, ns_ok,
                                      use_pallas=use_pallas)
            return buf, ()

        with jax.named_scope("bcast.cycle"):
            buf, _ = jax.lax.scan(cycle, buf, jnp.arange(num_cycles))
        return buf[None]   # leading device axis chunk of size 1

    out = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(axis),
                        check_vma=False)(packets)
    with jax.named_scope("bcast.unstack"):
        return _unstack(out, x)


def binomial_broadcast(x: jax.Array, mesh: Mesh, axis: str,
                       root: int = 0) -> jax.Array:
    """Whole-message binomial-tree broadcast: log2(n) ppermute rounds.
    The baseline the paper compares against; same stacked-output convention."""
    n = mesh.shape[axis]
    steps = max(1, (n - 1).bit_length())

    def body(xx):
        idx = jax.lax.axis_index(axis)
        vrank = (idx - root) % n
        with jax.named_scope("bcast.place"):
            buf = jnp.where(idx == root, xx, jnp.zeros_like(xx))
        have = (vrank == 0)
        for s in reversed(range(steps)):
            stride = 1 << s
            pairs = []
            for r in range(0, n, 2 * stride):
                if r + stride < n:
                    pairs.append((int((root + r) % n),
                                  int((root + r + stride) % n)))
            rec = jax.lax.ppermute(jnp.where(have, buf, jnp.zeros_like(buf)),
                                   axis, pairs)
            is_dst = (vrank % (2 * stride) == stride)
            buf = jnp.where(is_dst, rec, buf)
            have = have | is_dst
        with jax.named_scope("bcast.unstack"):
            return buf[None]

    return jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(axis),
                         check_vma=False)(x)


def chain_broadcast(x: jax.Array, mesh: Mesh, axis: str, root: int = 0,
                    num_packets: int = 8) -> jax.Array:
    """Pipelined ring/chain broadcast: packets stream rank->rank+1 (the
    MPICH 'pipeline' baseline), m + n - 2 ppermute rounds."""
    n = mesh.shape[axis]
    m = num_packets
    with jax.named_scope("bcast.place"):
        packets = _pad_packets(x, m)
    pairs = [(int((root + i) % n), int((root + i + 1) % n))
             for i in range(n - 1)]

    def body(pk):
        idx = jax.lax.axis_index(axis)
        vrank = (idx - root) % n
        with jax.named_scope("bcast.place"):
            buf = jnp.where(idx == root, pk, jnp.zeros_like(pk))

        def step(buf, s):
            # at step s, rank r forwards packet (s - r) if 0 <= s - r < m
            p = s - vrank
            ok = (p >= 0) & (p < m) & (vrank < n - 1)
            safe = jnp.clip(p, 0, m - 1)
            val = jnp.where(ok, buf[safe], jnp.zeros(buf.shape[1:], buf.dtype))
            rec = jax.lax.ppermute(val, axis, pairs)
            pr = s - vrank + 1
            rok = (pr >= 0) & (pr < m) & (vrank >= 1)
            rsafe = jnp.clip(pr, 0, m - 1)
            cur = buf[rsafe]
            buf = buf.at[rsafe].set(jnp.where(rok, rec, cur))
            return buf, ()

        buf, _ = jax.lax.scan(step, buf, jnp.arange(m + n - 2))
        return buf[None]

    out = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(axis),
                        check_vma=False)(packets)
    with jax.named_scope("bcast.unstack"):
        return _unstack(out, x)
