"""Plain numpy references, independent of the program under test.

``node_replay`` is the node program done one row at a time: each step
writes the received row where the step says, then reads the row it sends,
and folds that row's bits into a rotate-xor digest.
"""

from __future__ import annotations

import numpy as np


def rotxor(acc, bits):
    """Rotate ``acc`` left by one bit, then xor ``bits`` in (uint32)."""
    return ((acc << 1) | (acc >> 31)) ^ bits


def node_replay(buf0: np.ndarray, packets: np.ndarray, steps: np.ndarray):
    """Final buffer and sent-row digest of the steps ``(recv_ix, recv_ok,
    send_ix, send_ok, src)`` run over a copy of ``buf0``."""
    buf = buf0.copy()
    acc = np.zeros(buf.shape[1], np.uint32)
    zero = np.zeros(buf.shape[1], buf.dtype)
    for r_ix, r_ok, s_ix, s_ok, src in steps.tolist():
        if r_ok:
            buf[r_ix] = packets[src]
        val = buf[s_ix] if s_ok else zero
        acc = rotxor(acc, val.view(np.uint32))
    return buf, acc


def words_differing(a: np.ndarray, b: np.ndarray) -> int:
    """32-bit words whose bits differ between two arrays of one shape."""
    if a.shape != b.shape:
        raise ValueError(f"shapes differ: {a.shape} and {b.shape}")
    return int(np.count_nonzero(np.ascontiguousarray(a).view(np.uint32)
                                != np.ascontiguousarray(b).view(np.uint32)))
