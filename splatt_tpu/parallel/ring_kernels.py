"""Async ICI ring exchange — Pallas remote-copy ring sweeps (docs/ring.md).

The POINT2POINT path (parallel/ring.py) already gives the fine
decomposition O(dim/ndev) peak factor memory, but every ``ppermute``
hop is a barrier: the device finishes its masked pass over the local
nonzeros, THEN waits for the whole hop, THEN starts the next pass —
per-iteration wall-clock pays comm + compute in series.  The
reference's medium-grained MPI decomposition wins precisely by
streaming the row exchange while ranks compute (Isend/Irecv in
p_reduce_rows_point2point / p_update_rows_point2point,
src/mpi/mpi_cpd.c:323-546), and r04's bytes model showed the MTTKRP
kernel is bandwidth-bound — hiding the exchange is worth a full hop
time per step.

This module is the TPU-native version of that overlap: one Pallas
kernel per ring phase holds the entire ``ndev``-step loop, with the
factor row-block double-buffered in HBM and
``pltpu.make_async_remote_copy`` DMAs streaming block *s+1* from the
left neighbor while the compute for block *s* runs — the ICI DMA
engines move bytes concurrently with the VPU/MXU work, so a hop only
costs wall-clock when it is longer than the compute it hides under.

Double-buffer protocol (per device, per kernel; docs/ring.md has the
full lifecycle diagram):

- ``buf`` is a ``(2, block, R)`` HBM landing zone; step *s* computes on
  slot ``s % 2`` while the RDMA for step *s+1* lands in slot
  ``(s+1) % 2``.
- A **credit** (regular) semaphore implements backpressure: a device
  may start its step-*s* send only after consuming a credit granted by
  its RIGHT neighbor, and a device grants its LEFT neighbor a credit
  only once it has finished computing on (= freed) a slot AND retired
  its own send that sourced that slot.  Credits granted == sends, so
  the semaphore drains to zero — a leaked count would wedge the next
  collective.
- ``send_sems``/``recv_sems`` are the DMA-completion semaphores, one
  per slot: ``recv`` is waited before computing on a freshly received
  slot, ``send`` before a slot is handed back as a landing zone (and
  before the kernel retires).  Each DMA's send and recv side is waited
  exactly once.  Two of the left neighbor's sends can be in flight at
  once (into both slots), so one shared recv semaphore would let a
  wait for slot *s* be met by the bytes of slot *s+1* — TPU interpret
  mode, which runs a DMA only when its semaphore is waited on, read
  the unwritten slot that way.
- Step 0 opens with a neighbor barrier (``get_barrier_semaphore``) so
  no RDMA can land on a device that has not yet entered the kernel.

On a TPU, an ASYNC_RING CPD is refused before any device work
(:func:`splatt_tpu.parallel.sharded.refuse_async_ring_on_tpu`): the
exchanges match numpy on a v5e 2x2 at a small size (chip_smoke
--four-chips), but the CPD at 20M nonzeros finished no iteration in
359 s (PR 21).  Finding that stall is open (PERF.md).

Fallback ladder: the kernels only run on a real TPU backend
(:func:`async_ring_supported`); everywhere else — CPU tests and
interpret mode — :func:`async_ring_gather_rows` / :func:`async_blockwise_reduce_rows`
delegate to the ``ppermute`` implementations in
:mod:`splatt_tpu.parallel.ring`, so the ASYNC_RING comm strategy keeps
*today's semantics bit-for-bit* off-TPU and tier-1 exercises the exact
dataflow.  The kernels themselves are tested on CPU devices under TPU
interpret mode (``interpret=pltpu.InterpretParams(...)``), which
simulates the remote DMAs, semaphores and barrier and detects races.  A runtime failure of the async engine is degraded
classified by the driver (sharded.py): the comm engine is demoted
under its own shape key and the sweep rebuilds on the sync ring, then
all2all (``comm_fallback`` run-report events) — never an unhandled
exception.  The ``comm.ring_exchange`` fault site arms that ladder for
chaos drills.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from splatt_tpu.ops.mttkrp import mxu_precision
from splatt_tpu.utils import faults

#: nnz rows processed per in-kernel chunk of the gather/reduce compute
#: loops — sublane-aligned, small enough that the chunk working set
#: (indices + rows + one-hot tiles) stays a sliver of VMEM next to the
#: resident factor block.
_NNZ_CHUNK = 1024


def _pltpu():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu


@functools.cache
def async_ring_supported() -> bool:
    """Whether the Pallas remote-copy ring kernels would run here: only
    on a real TPU backend (interpret mode has no ICI).  Everywhere else
    the ASYNC_RING strategy uses the ppermute dataflow — same math,
    bit-for-bit — so selection never needs to fail off-TPU."""
    return jax.default_backend() == "tpu"


# -- kernel building blocks -------------------------------------------------


def _neighbor_barrier(pltpu, left, right):
    """Step-0 rendezvous: both neighbors must be inside the kernel
    (buffers + semaphores live) before any RDMA or credit signal may
    target them.  Signal both sides, wait for both — balanced, so the
    global barrier semaphore drains."""
    barrier = pltpu.get_barrier_semaphore()
    for nbr in (left, right):
        pltpu.semaphore_signal(barrier, inc=1, device_id=(nbr,),
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)


def _grant_credit(pltpu, credit_sem, left):
    pltpu.semaphore_signal(credit_sem, inc=1, device_id=(left,),
                           device_id_type=pltpu.DeviceIdType.MESH)


def _hop(pltpu, src_ref, dst_ref, src_slot, dst_slot, send_sems,
         recv_sems, right):
    """The step's remote copy descriptor: my ``src_ref[src_slot]``
    streams into the right neighbor's ``dst_ref[dst_slot]``.  Each slot
    has its own send and recv semaphore, so a wait retires exactly the
    DMA of that slot, never a later one that shares a count.
    Reconstructed with identical refs wherever its send/recv side is
    waited (the descriptor is just the address/semaphore tuple)."""
    return pltpu.make_async_remote_copy(
        src_ref=src_ref.at[src_slot], dst_ref=dst_ref.at[dst_slot],
        send_sem=send_sems.at[src_slot], recv_sem=recv_sems.at[dst_slot],
        device_id=(right,), device_id_type=pltpu.DeviceIdType.MESH)


def _stage(pltpu, src_ref, dst_ref, sem):
    """Blocking local (HBM<->VMEM) copy — the staging moves around the
    resident block; the REMOTE copies are the ones left in flight."""
    cp = pltpu.make_async_copy(src_ref, dst_ref, sem)
    cp.start()
    cp.wait()


# -- the TPU kernels --------------------------------------------------------
#
# Both kernels share the skeleton: grid=(ndev,) ring steps executed
# sequentially on the core, a (2, block, R) HBM comm buffer (a pallas
# output the caller discards), VMEM staging for the resident block,
# and an inner fori_loop over nnz chunks for the compute.  The gather
# kernel accumulates picked rows INTO the (nnz_pad, R) output across
# steps (read-modify-write through VMEM; the grid is sequential so the
# revisits cannot race); the reduce kernel accumulates the travelling
# (block, R) partial in VMEM and writes it once at the final step.


def _ring_gather_kernel(idx_div_ref, idx_loc_ref, u0_ref, rows_ref,
                        buf_ref, u_vmem, rows_vmem, div_vmem, loc_vmem,
                        local_sems, send_sems, recv_sems, credit_sem, *,
                        ndev: int, axis: str, block: int, nnz_pad: int):
    """One device's whole gather ring (≙ mpi_update_rows streamed).

    idx_div/idx_loc: (1, nnz_pad) int32 — owner shard and within-block
    row of each local nonzero's request (step-independent; only the
    ownership mask changes per step; pad entries carry owner -1 and
    match no shard).  u0: (block, R) my factor block.  rows (out):
    (nnz_pad, R) picked rows.  buf (out, discarded): (2, block, R)
    the double-buffered landing zone.
    """
    pltpu = _pltpu()
    s = pl.program_id(0)
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, ndev)
    left = jax.lax.rem(my + ndev - 1, ndev)
    cur = jax.lax.rem(s, 2)
    nchunks = nnz_pad // _NNZ_CHUNK

    @pl.when(s == 0)
    def _open():
        _neighbor_barrier(pltpu, left, right)
        # my own block seeds slot 0 (local HBM->HBM copy); slot 1 is a
        # free landing zone — grant the left neighbor its first credit
        _stage(pltpu, u0_ref, buf_ref.at[0], local_sems.at[0])
        _grant_credit(pltpu, credit_sem, left)

    @pl.when(s > 0)
    def _recv_wait():
        # the step-(s-1) hop delivered this slot
        _hop(pltpu, buf_ref, buf_ref, 1 - cur, cur, send_sems,
             recv_sems, right).wait_recv()

    @pl.when(s < ndev - 1)
    def _send():
        # backpressure: consume the credit the right neighbor granted
        # when it freed the destination slot, then stream my current
        # block forward — this DMA is what overlaps the compute below
        pltpu.semaphore_wait(credit_sem, 1)
        _hop(pltpu, buf_ref, buf_ref, cur, 1 - cur, send_sems,
             recv_sems, right).start()

    # stage the resident block for compute (HBM -> VMEM)
    _stage(pltpu, buf_ref.at[cur], u_vmem, local_sems.at[1])

    shard = jax.lax.rem(my - s + ndev, ndev)

    def chunk_body(c, _):
        lo = c * _NNZ_CHUNK
        # index streams live in HBM (ANY refs load only via DMA)
        _stage(pltpu, idx_div_ref.at[:, pl.ds(lo, _NNZ_CHUNK)], div_vmem,
               local_sems.at[2])
        _stage(pltpu, idx_loc_ref.at[:, pl.ds(lo, _NNZ_CHUNK)], loc_vmem,
               local_sems.at[2])
        div = div_vmem[...]                  # (1, C): a row, so no
        loc = loc_vmem[...]                  # in-kernel reshape
        mask = div == shard
        # one-hot row pick against the VMEM-resident block: the same
        # MXU-friendly contraction the single-chip engines use
        # ((block, C)ᵀ @ (block, R)).  Each nonzero matches exactly one
        # shard, so the cross-step accumulation only ever adds zeros —
        # bit-identical to a single gather.
        safe = jnp.where(mask, loc, 0)
        iota = jax.lax.broadcasted_iota(jnp.int32, (block, _NNZ_CHUNK), 0)
        onehot = ((safe == iota) & mask).astype(u_vmem.dtype)
        picked = jax.lax.dot_general(
            onehot, u_vmem[...],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=rows_vmem.dtype,
            precision=mxu_precision(u_vmem.dtype))

        @pl.when(s > 0)
        def _load():
            _stage(pltpu, rows_ref.at[pl.ds(lo, _NNZ_CHUNK)], rows_vmem,
                   local_sems.at[2])

        @pl.when(s == 0)
        def _zero():
            rows_vmem[...] = jnp.zeros_like(rows_vmem)

        rows_vmem[...] += picked
        _stage(pltpu, rows_vmem, rows_ref.at[pl.ds(lo, _NNZ_CHUNK)],
               local_sems.at[2])
        return 0

    jax.lax.fori_loop(0, nchunks, chunk_body, 0)

    # slot bookkeeping: my step-s send sourced buf[cur]; once it has
    # retired AND the compute above consumed the slot, hand it back to
    # the left neighbor as a landing zone.  Grants happen for the slots
    # a future send will actually target (steps 0..ndev-3); the final
    # step only drains the last in-flight send.
    @pl.when((s <= ndev - 3) & (s < ndev - 1))
    def _free():
        _hop(pltpu, buf_ref, buf_ref, cur, 1 - cur, send_sems,
             recv_sems, right).wait_send()
        _grant_credit(pltpu, credit_sem, left)

    @pl.when((s == ndev - 2) & (s < ndev - 1))
    def _retire_penultimate():
        # the second-to-last send is the LAST send; its slot is never
        # re-landed, so retire the DMA without granting a credit
        _hop(pltpu, buf_ref, buf_ref, cur, 1 - cur, send_sems,
             recv_sems, right).wait_send()


def _ring_reduce_kernel(idx_div_ref, idx_loc_ref, prod_ref, out_ref,
                        buf_ref, sbuf_ref, acc_vmem, blk_vmem, prod_vmem,
                        div_vmem, loc_vmem,
                        local_sems, send_sems, recv_sems, credit_sem, *,
                        ndev: int, axis: str, block: int, nnz_pad: int):
    """One device's whole reduce ring (≙ mpi_reduce_rows streamed).

    The partial destined for device d starts at device d+1 and travels
    RIGHT, each holder adding its local segment-sum for that block;
    after ndev-1 hops device d adds its own contribution and owns the
    fully reduced block.  Addition order around the ring differs from
    the fallback's psum (same math, different rounding order —
    docs/ring.md; the CPU fallback keeps psum semantics so tier-1
    parity stays bit-exact).

    Unlike the gather kernel — whose send source (slot ``cur``) and
    landing zone (slot ``1-cur``) are disjoint halves of ONE buffer —
    the reduce's outgoing partial is a fresh value each step, so it
    gets its own staging buffer ``sbuf``: every device stages into
    ``sbuf[cur]`` at step s while the left neighbor's RDMA lands in
    ``buf[(s+1) % 2]``; in-flight reads and incoming writes can never
    touch the same slot.  Both ``buf`` slots start free, so the left
    neighbor is granted min(2, ndev-1) credits up front and one more
    per folded (= freed) slot — grants == sends, every semaphore
    drains.

    prod: (nnz_pad, R) the Hadamard gather-product (zero-padded).
    out: (block, R) my reduced row-block, accumulator dtype.
    buf/sbuf (outs, discarded): (2, block, R) recv landing zone /
    send staging.
    """
    pltpu = _pltpu()
    s = pl.program_id(0)
    my = jax.lax.axis_index(axis)
    right = jax.lax.rem(my + 1, ndev)
    left = jax.lax.rem(my + ndev - 1, ndev)
    cur = jax.lax.rem(s, 2)
    nchunks = nnz_pad // _NNZ_CHUNK

    def hop_in():
        # the left neighbor's step-(s-1) send: its sbuf[1-cur] into my
        # buf[cur] (the descriptor is symmetric under SPMD, so the same
        # refs reconstruct both wait sides)
        return _hop(pltpu, sbuf_ref, buf_ref, 1 - cur, cur, send_sems,
                    recv_sems, right)

    @pl.when(s == 0)
    def _open():
        _neighbor_barrier(pltpu, left, right)
        # both landing slots start free: grant their credits up front
        # (min(2, ndev-1): never more credits than sends)
        _grant_credit(pltpu, credit_sem, left)

        @pl.when(ndev > 2)
        def _second():
            _grant_credit(pltpu, credit_sem, left)

    @pl.when(s > 0)
    def _recv_wait():
        hop_in().wait_recv()

    # local partial for the block this step handles: j = (my - 1 - s)
    # mod ndev — the chunk that ends at its owner after the remaining
    # hops (standard ring reduce-scatter schedule)
    j = jax.lax.rem(my - 1 - s + 2 * ndev, ndev)

    def chunk_body(c, _):
        lo = c * _NNZ_CHUNK
        _stage(pltpu, idx_div_ref.at[:, pl.ds(lo, _NNZ_CHUNK)], div_vmem,
               local_sems.at[2])
        _stage(pltpu, idx_loc_ref.at[:, pl.ds(lo, _NNZ_CHUNK)], loc_vmem,
               local_sems.at[2])
        div = div_vmem[...]                  # (1, C): a row, so no
        loc = loc_vmem[...]                  # in-kernel reshape
        _stage(pltpu, prod_ref.at[pl.ds(lo, _NNZ_CHUNK)], prod_vmem,
               local_sems.at[1])
        mask = div == j
        safe = jnp.where(mask, loc, 0)
        iota = jax.lax.broadcasted_iota(jnp.int32, (block, _NNZ_CHUNK), 0)
        onehot = ((safe == iota) & mask).astype(acc_vmem.dtype)
        part = jax.lax.dot_general(
            onehot, prod_vmem[...].astype(acc_vmem.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=acc_vmem.dtype,
            precision=mxu_precision(acc_vmem.dtype))

        @pl.when(c == 0)
        def _init():
            acc_vmem[...] = part

        @pl.when(c != 0)
        def _acc():
            acc_vmem[...] += part

        return 0

    jax.lax.fori_loop(0, nchunks, chunk_body, 0)

    @pl.when(s > 0)
    def _fold():
        # fold in the travelling partial that just arrived
        _stage(pltpu, buf_ref.at[cur], blk_vmem, local_sems.at[2])
        acc_vmem[...] += blk_vmem[...]

    @pl.when(s < ndev - 1)
    def _send():
        # stage acc into MY send slot and stream it into the right
        # neighbor's landing slot; the DMA overlaps the NEXT step's
        # local partial computation.  sbuf[cur]'s previous send (step
        # s-2) was retired at step s-1, so re-staging is safe.
        pltpu.semaphore_wait(credit_sem, 1)
        _stage(pltpu, acc_vmem, sbuf_ref.at[cur], local_sems.at[0])
        _hop(pltpu, sbuf_ref, buf_ref, cur, 1 - cur, send_sems,
             recv_sems, right).start()

    @pl.when((s > 0) & (s <= ndev - 2))
    def _retire():
        # retire my step-(s-1) send (sbuf[1-cur] is re-staged at s+1)
        hop_in().wait_send()

    @pl.when((s >= 1) & (s <= ndev - 3))
    def _grant():
        # the fold consumed buf[cur]: hand it back to the left
        # neighbor as a landing zone (its send s+1 targets this slot).
        # Together with _open's up-front credits, grants == sends.
        _grant_credit(pltpu, credit_sem, left)

    @pl.when(s == ndev - 1)
    def _close():
        # my block is fully reduced: publish it and retire the FINAL
        # send (step ndev-2, sourced from sbuf[1-cur]) so the kernel
        # ends with every semaphore drained
        _stage(pltpu, acc_vmem, out_ref, local_sems.at[0])

        @pl.when(ndev > 1)
        def _():
            hop_in().wait_send()


def _pad_streams(idx: jax.Array, block: int):
    """(idx // block, idx % block) padded to whole _NNZ_CHUNKs with an
    owner id of -1 (matches no shard: padding rows contribute zero).
    The stream widens through the blocked format's stream-consumer
    boundary (blocked.widen_ids — the same interface the single-chip
    engines decode through), so a narrow encoded shard stream flows
    into the ring kernels unchanged."""
    from splatt_tpu.blocked import widen_ids
    from splatt_tpu.utils.env import ceil_to

    n = int(idx.shape[0])
    n_pad = max(_NNZ_CHUNK, ceil_to(n, _NNZ_CHUNK))
    padded = jnp.pad(widen_ids(idx), (0, n_pad - n))
    div = jnp.where(jnp.arange(n_pad) < n, padded // block, -1)
    return div.astype(jnp.int32), jnp.mod(padded, block), n_pad


def _ring_compiler_params(collective_id: int):
    from jax.experimental.pallas import tpu as pltpu

    from splatt_tpu.ops.pallas_kernels import _vmem_limit

    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(),
                                collective_id=collective_id,
                                has_side_effects=True)


def _gather_pallas(U_l: jax.Array, idx: jax.Array, axis: str,
                   ndev: int, interpret=False) -> jax.Array:
    """TPU path of :func:`async_ring_gather_rows`.  `interpret` takes
    a ``pltpu.InterpretParams``: TPU interpret mode simulates the remote
    DMAs and semaphores on CPU devices (the tests' only way to run the
    ring protocol off the chip)."""
    from splatt_tpu.utils.env import ceil_to

    pltpu = _pltpu()
    block, R0 = int(U_l.shape[0]), int(U_l.shape[1])
    # rank padded to whole 128-lane tiles: Mosaic slices the HBM comm
    # buffer per slot only along tile-aligned lanes
    R = ceil_to(R0, 128)
    U_l = jnp.pad(U_l, ((0, 0), (0, R - R0)))
    div, loc, nnz_pad = _pad_streams(idx, block)
    kernel = functools.partial(_ring_gather_kernel, ndev=ndev, axis=axis,
                               block=block, nnz_pad=nnz_pad)
    rows, _ = pl.pallas_call(
        kernel,
        grid=(ndev,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        out_shape=(jax.ShapeDtypeStruct((nnz_pad, R), U_l.dtype),
                   jax.ShapeDtypeStruct((2, block, R), U_l.dtype)),
        scratch_shapes=(
            pltpu.VMEM((block, R), U_l.dtype),
            pltpu.VMEM((_NNZ_CHUNK, R), U_l.dtype),
            pltpu.VMEM((1, _NNZ_CHUNK), jnp.int32),
            pltpu.VMEM((1, _NNZ_CHUNK), jnp.int32),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ),
        compiler_params=_ring_compiler_params(7),
        interpret=interpret,
    )(div[None, :], loc[None, :], U_l)
    return rows[:int(idx.shape[0]), :R0]


def _reduce_pallas(prod: jax.Array, idx: jax.Array, axis: str, ndev: int,
                   block: int, interpret=False) -> jax.Array:
    """TPU path of :func:`async_blockwise_reduce_rows`; `interpret` as
    in :func:`_gather_pallas`."""
    pltpu = _pltpu()
    from splatt_tpu.ops.mttkrp import acc_dtype

    from splatt_tpu.utils.env import ceil_to

    R0 = int(prod.shape[1])
    R = ceil_to(R0, 128)     # whole lane tiles, as in _gather_pallas
    out_dtype = acc_dtype(prod.dtype)
    div, loc, nnz_pad = _pad_streams(idx, block)
    n = int(prod.shape[0])
    prod_pad = jnp.pad(prod, ((0, nnz_pad - n), (0, R - R0)))
    kernel = functools.partial(_ring_reduce_kernel, ndev=ndev, axis=axis,
                               block=block, nnz_pad=nnz_pad)
    out, _, _ = pl.pallas_call(
        kernel,
        grid=(ndev,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        out_shape=(jax.ShapeDtypeStruct((block, R), out_dtype),
                   jax.ShapeDtypeStruct((2, block, R), out_dtype),
                   jax.ShapeDtypeStruct((2, block, R), out_dtype)),
        scratch_shapes=(
            pltpu.VMEM((block, R), out_dtype),
            pltpu.VMEM((block, R), out_dtype),
            pltpu.VMEM((_NNZ_CHUNK, R), prod.dtype),
            pltpu.VMEM((1, _NNZ_CHUNK), jnp.int32),
            pltpu.VMEM((1, _NNZ_CHUNK), jnp.int32),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ),
        compiler_params=_ring_compiler_params(8),
        interpret=interpret,
    )(div[None, :], loc[None, :], prod_pad)
    return out[:, :R0]


# -- public entry points (what make_sharded_sweep calls) --------------------


def async_ring_gather_rows(U_l: jax.Array, idx: jax.Array, axis: str,
                           ndev: int) -> jax.Array:
    """Rows of a row-sharded factor at global ids `idx` via the async
    remote-copy ring; ≡ :func:`splatt_tpu.parallel.ring.ring_gather_rows`
    mathematically (each id matches exactly one shard, so the
    cross-step accumulation only adds zeros — exact).

    The ``comm.ring_exchange`` fault site arms here (trace time — the
    sweep's first invocation), so chaos drills exercise the driver's
    comm-fallback ladder exactly where a real Mosaic/RDMA failure would
    surface.
    """
    faults.maybe_fail("comm.ring_exchange")
    if ndev >= 2 and async_ring_supported():
        return _gather_pallas(U_l, idx, axis, ndev)
    # interpret/CPU fallback: today's ppermute semantics, bit-for-bit
    # (docs/ring.md fallback ladder) — tier-1 exercises this dataflow
    from splatt_tpu.parallel.ring import ring_gather_rows

    return ring_gather_rows(U_l, idx, axis, ndev)


def async_blockwise_reduce_rows(prod: jax.Array, idx: jax.Array, axis: str,
                                ndev: int, block: int) -> jax.Array:
    """Row-sharded MTTKRP output via the async reduce ring.  On TPU the
    partial travels the ring accumulating in hop order (different
    rounding ORDER than psum, same math — docs/ring.md); off-TPU it
    delegates to the psum formulation so CPU parity stays bit-exact
    with the POINT2POINT path."""
    faults.maybe_fail("comm.ring_exchange")
    if ndev >= 2 and async_ring_supported():
        return _reduce_pallas(prod, idx, axis, ndev, block)
    from splatt_tpu.parallel.ring import blockwise_reduce_rows

    return blockwise_reduce_rows(prod, idx, axis, ndev, block)
