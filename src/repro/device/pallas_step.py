"""Pallas-backed packed round step for the ppermute cycle loop.

Between two collective-permutes every device does a scatter (write the row
it just received) followed by a gather (read the row it sends next). The
XLA rendering is a ``dynamic_update_index_in_dim`` + ``dynamic_index_in_dim``
pair — two full passes over the packet buffer's touched rows plus the copy
XLA inserts when the buffer cannot be donated mid-loop. The packed step
fuses both into one kernel with the buffer aliased in place in HBM
(``input_output_aliases``); the whole buffer passes through VMEM on every
call, which bounds the buffer size (ROADMAP S3).

Same contract as the jnp reference (`round_step_ref`): indexes are
pre-clipped, masks decide whether the write/read actually happens, so the
two paths are bit-identical. The backend decides how the kernel runs: on
the CPU it runs in Pallas interpret mode (tests), on a TPU Mosaic compiles
it. There is no silent switch to the jnp step: a buffer the kernel cannot
take raises ``ValueError`` (:func:`check_kernel_limits`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM the kernel asks Mosaic for (a TPU v5e core has 128 MiB; the
# compiler's default scope is 16 MiB). The whole packet buffer is one VMEM
# block, staged in and out, so the buffer may take a bit under half of it.
VMEM_LIMIT_BYTES = 100 << 20
# Mosaic indexes a row of a 2-D VMEM block dynamically only when one row is
# one sublane: 16- and 8-bit dtypes pack 2 or 4 rows per sublane, and a
# dynamic row index there is refused ("cannot statically prove that index
# in dimension 0 is a multiple of 8").
ROW_ITEMSIZE = 4
# An array's last dimension is tiled in lanes of 128; the dimension before
# it in sublanes, 8 of 32-bit words and more of narrower ones (a tile is
# 4 KiB whatever the dtype).
LANES = 128


def sublanes(itemsize: int) -> int:
    """Rows of one (sublane, lane) tile for a dtype of ``itemsize`` bytes."""
    return 32 // itemsize


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tiled_bytes(shape, itemsize: int) -> int:
    """Bytes of an array of ``shape`` laid out in whole tiles: the last
    dimension rounded up to lanes, the one before it (if any) to a tile's
    sublanes."""
    *lead, minor = shape
    words = _round_up(minor, LANES)
    if lead:
        words *= math.prod(lead[:-1]) * _round_up(lead[-1],
                                                  sublanes(itemsize))
    return words * itemsize


def kernel_vmem_bytes(shape, itemsize: int = 4) -> int:
    """VMEM the kernel stages for a packet buffer of ``shape`` (rows first,
    then the shape of one row): the buffer in and out, and the received
    and the sent row, each tiled by the shape of a row."""
    return 2 * _tiled_bytes(shape, itemsize) \
        + 2 * _tiled_bytes(shape[1:], itemsize)


def check_kernel_limits(shape, dtype) -> None:
    """Raise ``ValueError`` naming the limit a packet buffer breaks: rows
    of a dtype narrower than 32 bits, or more VMEM than
    ``VMEM_LIMIT_BYTES``. Such buffers run only on the jnp step, which
    the caller selects (``use_pallas=False``)."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize != ROW_ITEMSIZE:
        raise ValueError(
            f"Pallas round step: {dtype} rows are packed several to a "
            f"sublane and cannot be indexed dynamically; the kernel takes "
            f"{ROW_ITEMSIZE}-byte dtypes only (use_pallas=False for "
            f"{dtype})")
    need = kernel_vmem_bytes(shape, dtype.itemsize)
    if need > VMEM_LIMIT_BYTES:
        dims = "x".join(str(d) for d in shape)
        raise ValueError(
            f"Pallas round step: a {dims} {dtype} packet buffer "
            f"needs {need} bytes of VMEM, over the kernel's VMEM budget of "
            f"VMEM_LIMIT_BYTES={VMEM_LIMIT_BYTES} (use_pallas=False for "
            f"this size)")


def round_step_ref(buf, rec, r_idx, r_ok, s_idx, s_ok):
    """Scatter the received row into ``buf``, then gather the next send row.

    ``r_idx``/``s_idx`` must already be clipped to [0, buf.shape[0]);
    ``r_ok``/``s_ok`` gate the write and zero the read respectively."""
    cur = jax.lax.dynamic_index_in_dim(buf, r_idx, keepdims=False)
    new = jnp.where(r_ok, rec, cur)
    buf = jax.lax.dynamic_update_index_in_dim(buf, new, r_idx, 0)
    val = jax.lax.dynamic_index_in_dim(buf, s_idx, keepdims=False)
    val = jnp.where(s_ok, val, jnp.zeros_like(val))
    return buf, val


def _scatter_gather_kernel(scal_ref, buf_ref, rec_ref, out_ref, val_ref):
    # scal = [r_idx, r_ok, s_idx, s_ok]; rec and val are one row, (1,) + the
    # row's shape, indexed like a row of the buffer (dimension 0). Aliasing
    # makes the HBM buffer in place, not the VMEM blocks: out is a block of
    # its own, and inside a scan on a v5e it does not start as buf (a root
    # that only sends got back a zeroed buffer), so copy buf first. The
    # gather reads *after* the scatter so an intra-cycle forward (send a row
    # received one sub-round earlier) sees the fresh value.
    out_ref[...] = buf_ref[...]

    @pl.when(scal_ref[1] != 0)
    def _write():
        out_ref[pl.ds(scal_ref[0], 1), ...] = rec_ref[...]

    v = out_ref[pl.ds(scal_ref[2], 1), ...]
    val_ref[...] = jnp.where(scal_ref[3] != 0, v, jnp.zeros_like(v))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _round_step_pallas(buf, rec, scal, interpret=False):
    # the rows travel as (1,) + row-shape blocks, laid out like a buffer row
    out, val = pl.pallas_call(
        _scatter_gather_kernel,
        out_shape=(jax.ShapeDtypeStruct(buf.shape, buf.dtype),
                   jax.ShapeDtypeStruct((1,) + rec.shape, rec.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="bcast_round_step",
    )(scal, buf, rec[None])
    return out, val[0]


def round_step(buf, rec, r_idx, r_ok, s_idx, s_ok, *, use_pallas=False):
    """The packed scatter+gather step: the jnp reference by default, the
    Pallas kernel when ``use_pallas`` (compiled on a TPU, interpreted on
    the CPU). Both paths run under the ``bcast.step`` scope."""
    with jax.named_scope("bcast.step"):
        if not use_pallas:
            return round_step_ref(buf, rec, r_idx, r_ok, s_idx, s_ok)
        check_kernel_limits(buf.shape, buf.dtype)
        scal = jnp.stack([jnp.int32(r_idx), jnp.int32(r_ok),
                          jnp.int32(s_idx), jnp.int32(s_ok)])
        return _round_step_pallas(buf, rec, scal,
                                  interpret=jax.default_backend() == "cpu")
