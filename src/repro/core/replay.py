"""Plain numpy reference of a broadcast's device schedule.

``replay_broadcast`` runs a ``DeviceSchedule``'s tables node by node, with
no device and no folding: every pair of every sub-round is one row copy
from the sender's packet buffer to the receiver's, cycle by cycle. It reads
only the schedule's fields (``perms``, ``send_rel``/``recv_rel``,
``send_abs``/``recv_abs``, ``K``, ``num_relay``, ``root``,
``num_cycles``), so it imports nothing of the device path. A sound
schedule leaves the root's packet rows in every node's buffer.
"""

from __future__ import annotations

import numpy as np

_NOSEND = -(10 ** 6)   # DeviceSchedule's mark of a node that does not send


def replay_broadcast(sched, num_groups: int, packets: np.ndarray
                     ) -> np.ndarray:
    """Every node's final buffer, ``(num_nodes, m*K + num_relay) +
    row shape``, after the schedule's cycles with ``packets`` (the root's
    ``m*K`` packet rows) on the root and zeros everywhere else. A row that
    a sender does not hold this cycle is sent as zeros, and a receiver
    whose row does not hold leaves its buffer as it is (the device sends
    some row there, and sinks it: the packet rows come out the same)."""
    total = num_groups * sched.K
    if packets.shape[0] != total:
        raise ValueError(f"{packets.shape[0]} packet rows; the schedule "
                         f"sends {total}")
    n = len(sched.send_rel[0])
    bufs = np.zeros((n, total + sched.num_relay) + packets.shape[1:],
                    packets.dtype)
    bufs[sched.root, :total] = packets

    def row(rel, ab, c):
        if ab >= 0:
            return total + ab
        pk = c * sched.K + rel
        return pk if rel != _NOSEND and 0 <= pk < total else None

    for c in range(sched.num_cycles(num_groups)):
        for r, pairs in enumerate(sched.perms):
            moves = []
            for u, v in pairs:
                s = row(sched.send_rel[r][u], sched.send_abs[r][u], c)
                val = (bufs[u, s].copy() if s is not None
                       else np.zeros_like(packets[0]))
                moves.append((v, row(sched.recv_rel[r][v],
                                     sched.recv_abs[r][v], c), val))
            for v, d, val in moves:
                if d is not None:
                    bufs[v, d] = val
    return bufs
