"""Execute device schedules as ``lax.ppermute`` programs under shard_map.

The cycle loop is a ``lax.scan`` (compile size independent of message size);
the d sub-rounds within a cycle are unrolled (d is small: 1-8 for the BBS
families). With one fabric node per device each sub-round is a matching
=> exactly one XLA ``collective-permute``; between permutes every device
runs the packed scatter+gather step (``repro.device.pallas_step``). This
is the TPU-native rendering of the paper's algorithm: every ICI link
carries a packet every round — balanced saturation. A fabric with more
nodes than devices is folded onto them (``repro.device.schedule.
fold_schedule``): a hop between two nodes of one device is a row move in
its memory, and the hops between two devices travel as stacked rows, one
collective permute per chip-level permutation.

``device_mesh`` builds the execution mesh from whatever devices the process
has: the chips of a TPU host, or on the CPU emulated host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` **set before jax
initializes** — the device count cannot change afterwards, so tests spawn
a subprocess, see tests/test_device.py and docs/device.md).
``node_coords`` maps fabric nodes to chip coordinates and ``far_pairs``
names the pairs that join chips without a link between them.

The three runners name their phases with ``jax.named_scope``, so that a
profiler trace or the compiled HLO (``op_name`` metadata) tells them apart:
``bcast.place`` (padding into packet rows, relay rows, zeroing the non-root
copies), ``bcast.cycle`` (the scan), ``bcast.step`` (the round step, set in
``repro.device.pallas_step.round_step``, and the folded cycle's row reads
and writes), ``bcast.local`` (the folded cycle's moves between nodes of
one device) and ``bcast.unstack`` (the stacked output cut back to the
payload); a collective permute is found by its opcode. Scopes are
metadata only: the optimized program is the same.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.device.pallas_step import LANES, round_step, sublanes
from repro.device.schedule import (_NOSEND, DeviceSchedule, FoldedSchedule,
                                   RowTable, fold_schedule)


def device_mesh(num_devices: int, axis: str = "dev") -> Mesh:
    """A 1-D mesh over the first ``num_devices`` process devices, in
    ``jax.devices()`` order."""
    devs = jax.devices()
    if len(devs) < num_devices:
        platform = devs[0].platform
        hint = (f"; emulate host devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={num_devices} set "
                f"before jax initializes" if platform == "cpu" else "")
        raise RuntimeError(
            f"the mesh needs {num_devices} devices; the "
            f"{platform} platform has {len(devs)}{hint}")
    return Mesh(np.array(devs[:num_devices]), (axis,))


def node_coords(topo, devices) -> Dict[int, Tuple[int, ...]]:
    """Physical chip coordinates of each fabric node, its nodes folded
    onto ``devices`` in contiguous blocks as ``bbs_broadcast`` folds them
    (node ``v`` runs on ``devices[v // (num_nodes / len(devices))]``; one
    node per device in the ``device_mesh`` order).

    On a v5e 2x2 host, ``jax.devices()`` enumerates (0,0), (1,0), (0,1),
    (1,1): every edge of ``torus2d(2, 2)`` lies on a link, two of the four
    edges of ``ring(4)`` join diagonal chips, and so do a Dragonfly's
    global pairs between groups 0 and 3 and groups 1 and 2 with group g on
    chip g. ``far_pairs`` names the pairs whose rows cross two links."""
    if topo.num_nodes % len(devices):
        raise ValueError(
            f"{topo.name} has {topo.num_nodes} nodes, which do not fold "
            f"onto {len(devices)} devices in equal blocks")
    per = topo.num_nodes // len(devices)
    return {v: tuple(int(c) for c in devices[v // per].coords)
            for v in range(topo.num_nodes)}


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
    """Broadcast `x` from the schedule's root node to every fabric node,
    the nodes folded onto the devices along `axis` in contiguous blocks
    (``fold_schedule``). Returns every node's copy stacked on a leading
    axis, ``(num_nodes,) + x.shape``.

    The input is only read on the root's device; other devices' values are
    ignored (zeroed before the pipeline runs). Relay rows (multi-hop plan
    edges) live after the ``m*K`` packet rows and are dropped on return.
    A device holds the packet buffers of all its nodes end to end; with
    one node per device the program is the single-row cycle of the round
    step, otherwise the folded cycle (``_folded_cycle``), whose node
    buffers end in one more row, where writes that hold no row go.
    """
    fold = fold_schedule(sched, mesh.shape[axis])
    per = fold.nodes_per_chip
    if use_pallas and per > 1:
        raise ValueError(
            "the Pallas round step holds one node's packet buffer in VMEM; "
            "a device with several nodes runs the jnp step "
            "(use_pallas=False)")
    m = num_groups
    K = sched.K
    total = m * K
    rows = total + sched.num_relay + (per > 1)
    root_chip, root_slot = divmod(sched.root, per)
    with jax.named_scope("bcast.place"):
        packets = _pad_packets(x, total)
        if sched.num_relay:
            packets = jnp.concatenate(
                [packets, jnp.zeros((sched.num_relay,) + packets.shape[1:],
                                    packets.dtype)])
    num_cycles = sched.num_cycles(m)

    def body(buf_x):
        idx = jax.lax.axis_index(axis)
        with jax.named_scope("bcast.place"):
            buf = jnp.where(idx == root_chip, buf_x, jnp.zeros_like(buf_x))
            if per > 1:
                buf = jnp.pad(buf, ((root_slot * rows,
                                     (per - root_slot) * rows
                                     - buf.shape[0]),)
                              + ((0, 0),) * (buf.ndim - 1))
        if per == 1:
            cycle = _node_cycle(sched, total, idx, axis, use_pallas)
            xs = jnp.arange(num_cycles)
        else:
            cycle, xs = _folded_cycle(fold, total, rows, idx, axis,
                                      num_cycles)
        with jax.named_scope("bcast.cycle"):
            buf, _ = jax.lax.scan(cycle, buf, xs)
        # the device's nodes on a leading axis
        return buf.reshape((per, rows) + buf.shape[1:])

    out = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(axis),
                        check_vma=False)(packets)
    with jax.named_scope("bcast.unstack"):
        return _unstack(out, x)


def _rows(rel, ab, c, K: int, total: int):
    """(index, holds) of schedule rows at cycle ``c``: packet ``c*K + rel``
    where it lies in [0, total), relay slot ``abs`` (after the packet rows)
    where ``abs >= 0``; the index is clipped into the packet rows where the
    row does not hold."""
    pk = c * K + rel
    ok = (ab >= 0) | ((rel != _NOSEND) & (pk >= 0) & (pk < total))
    ix = jnp.where(ab >= 0, total + ab, jnp.clip(pk, 0, total - 1))
    return ix, ok


def _node_cycle(sched: DeviceSchedule, total: int, idx, axis: str,
                use_pallas: bool):
    """One node per device: each sub-round one collective permute of one
    row, then the round step writes the received row and reads the next
    one to send."""
    K = sched.K
    send_rel = jnp.asarray(sched.send_rel)
    recv_rel = jnp.asarray(sched.recv_rel)
    send_abs = jnp.asarray(sched.send_abs)
    recv_abs = jnp.asarray(sched.recv_abs)

    def slot(r, c):
        """(send_idx, send_ok, recv_idx, recv_ok) for sub-round r."""
        s_ix, s_ok = _rows(send_rel[r, idx], send_abs[r, idx], c, K, total)
        r_ix, r_ok = _rows(recv_rel[r, idx], recv_abs[r, idx], c, K, total)
        return s_ix, s_ok, r_ix, r_ok

    def cycle(buf, c):
        s_ix, s_ok, _, _ = slot(0, c)
        zero = jnp.zeros(buf.shape[1:], buf.dtype)
        buf, val = round_step(buf, zero, 0, False, s_ix, s_ok,
                              use_pallas=use_pallas)
        for r in range(sched.d):
            rec = jax.lax.ppermute(val, axis, sched.perms[r])
            _, _, r_ix, r_ok = slot(r, c)
            if r + 1 < sched.d:
                ns_ix, ns_ok, _, _ = slot(r + 1, c)
            else:
                ns_ix, ns_ok = 0, jnp.bool_(False)
            buf, val = round_step(buf, rec, r_ix, r_ok, ns_ix, ns_ok,
                                  use_pallas=use_pallas)
        return buf, ()

    return cycle


def _take_rows(buf, ix):
    """``buf[ix]`` for a vector of row indices that lie in the buffer."""
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, buf.ndim)), collapsed_slice_dims=(0,),
        start_index_map=(0,))
    return jax.lax.gather(buf, ix[:, None], dims, (1,) + buf.shape[1:],
                          mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _put_rows(buf, ix, rows):
    """``buf`` with ``rows[i]`` written at row ``ix[i]`` (indices in the
    buffer)."""
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, buf.ndim)),
        inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,))
    return jax.lax.scatter(buf, ix[:, None], rows, dims,
                           mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


def _folded_cycle(fold: FoldedSchedule, total: int, rows: int, idx,
                  axis: str, num_cycles: int):
    """Several nodes per device, their packet buffers end to end (node slot
    ``s`` owns rows ``[s*rows, (s+1)*rows)``, the last of them a sink that
    is never read). Each sub-round the device reads every row its nodes
    send in one gather, hands the rows between its own nodes with one
    scatter (``bcast.local``), sends the rows bound for other devices as
    one stack per chip-level permutation, and writes the stacks it
    receives (``bcast.step``).

    Returns the cycle and what the scan feeds it: the buffer index of
    every table entry of every cycle, computed once for the whole
    broadcast, so that the loop holds no index arithmetic and every index
    lies in the buffer. A write whose row does not hold this cycle goes to
    its node's sink row; a read whose row does not hold reads a row whose
    receiver sinks it (a relay slot may take such a row, and forwards it
    to a receiver that sinks it in the same cycle)."""
    tables, reads, layout = [], [], []

    def entries(t, read):
        tables.append(t)
        reads.append(np.full(t.width, read))
        end = sum(x.width for x in tables)
        return slice(end - t.width, end)

    for rnd in fold.rounds:
        first = sum(x.width for x in tables)
        for p in rnd.permutes:
            entries(p.send, True)
        entries(rnd.local_send, True)
        sent = slice(first, sum(x.width for x in tables))
        local = None
        if rnd.local_recv.width:
            # the local scatter takes every row read; those bound for
            # other devices go to the sink
            shape = (fold.chips, sum(p.send.width for p in rnd.permutes))
            pad = (np.zeros(shape, np.int64), np.full(shape, _NOSEND),
                   np.full(shape, -1))
            local = entries(RowTable(*(
                np.concatenate([a, getattr(rnd.local_recv, f)], axis=1)
                for a, f in zip(pad, ("slot", "rel", "abs")))), False)
        layout.append((sent, local,
                       [(p.pairs, p.send.width, entries(p.recv, False))
                        for p in rnd.permutes]))
    slot, rel, ab = (jnp.asarray(np.concatenate(
        [getattr(t, f) for t in tables], axis=1))[idx]
        for f in ("slot", "rel", "abs"))
    ix, ok = _rows(rel, ab, jnp.arange(num_cycles)[:, None],
                   fold.schedule.K, total)
    ix = slot * rows + jnp.where(ok | np.concatenate(reads), ix, rows - 1)

    def cycle(buf, ix):
        with jax.named_scope("bcast.step"):
            sent = _take_rows(buf, ix[layout[0][0]])
        for r, (_, local, perms) in enumerate(layout):
            got, off = [], 0
            for pairs, w, recv in perms:
                got.append((recv, jax.lax.ppermute(sent[off:off + w], axis,
                                                   pairs)))
                off += w
            if local is not None:
                with jax.named_scope("bcast.local"):
                    buf = _put_rows(buf, ix[local], sent)
            with jax.named_scope("bcast.step"):
                for recv, rec in got:
                    buf = _put_rows(buf, ix[recv], rec)
                if r + 1 < len(layout):
                    sent = _take_rows(buf, ix[layout[r + 1][0]])
        return buf, ()

    return cycle, ix


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
