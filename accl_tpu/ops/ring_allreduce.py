"""Fused ring allreduce as a single Pallas TPU kernel.

The performance form of the eager segmented ring allreduce
(ccl_offload_control.c:1888-2071): where the lax schedule in
sequencer/schedules.py emits one XLA collective-permute per hop, this
kernel drives the ICI links directly with async remote DMAs
(pltpu.make_async_remote_copy) and fuses the recv-reduce step
(.c:755-789's fused recv-reduce-send) into the same VMEM pass — no HBM
round-trip between hops.

Structure per device: P-1 reduce-scatter hops (accumulator travels the
ring, each hop adds the local copy of the arriving chunk) then P-1
allgather hops (reduced chunks relay around). Double-slotted comm buffers
+ DMA semaphores provide the rx-ring discipline the reference implements
in rxbuf_offload.

Runs under shard_map. Lowered for a TPU mesh it compiles to Mosaic; lowered
for a CPU mesh it executes in Pallas TPU interpret mode, which also gives
schedule race detection (InterpretParams detect_races) — see
tests/test_pallas_kernels.py. The caller, who owns the mesh, picks the mode
with `interpret_for(mesh)`.

The ring is a cycle of mesh positions (`ring`), not mesh index +- 1: the
caller embeds it in the chips' physical torus with `torus_ring(devices)`,
so every hop crosses one link whatever order the mesh lists its devices
in (NCCL's and ACCL's rank tables do the same).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..constants import ReduceFunction

# Per-kernel segment slots: each slot owns a distinct collective_id, so
# its neighbor-barrier semaphore (and, in interpret mode, every piece of
# collective_id-keyed shared state) is private to the slot. Consecutive
# large-payload segments then double-buffer across slots — the
# segmenter/rx-ring overlap of the reference — instead of serializing on
# one shared id. collective_id layout: unidirectional kernel slots take
# the even ids (2*slot), the bidirectional kernel the odd (2*slot + 1).
NUM_RING_SLOTS = 2


def mesh_on_tpu(mesh) -> bool:
    """Whether programs over `mesh` are lowered for TPUs: the platform of
    the mesh's devices, not the process's default backend (a CPU process
    may lower for a described TPU topology). A device-less mesh lowers
    for no platform."""
    return (mesh.devices is not None
            and mesh.devices.flat[0].platform == "tpu")


def interpret_for(mesh, detect_races: bool = False):
    """The ring kernels' `interpret` for a program lowered over `mesh`:
    Mosaic (False) when the mesh's devices are TPUs, the TPU interpreter
    (optionally race-detecting) otherwise."""
    if mesh_on_tpu(mesh):
        return False
    return pltpu.InterpretParams(detect_races=detect_races)


# Largest world whose ring is searched: the chips of one host. Larger
# meshes keep their own order.
RING_SEARCH_MAX = 16


def _chip_coords(devices) -> list[tuple[int, ...]] | None:
    """Each device's position in the chips' torus, or None where a device
    has none (CPU devices)."""
    coords = [getattr(d, "coords", None) for d in devices]
    if any(c is None for c in coords):
        return None
    return [tuple(c) for c in coords]


def _linked(a: tuple[int, ...], b: tuple[int, ...]) -> bool:
    """Torus neighbours: coordinates one apart in exactly one dimension.
    No wraparound link is assumed; a one-host slice has none."""
    return sum(abs(x - y) for x, y in zip(a, b)) == 1


def ring_detours(devices, ring: tuple[int, ...]) -> int:
    """Hops of `ring` (mesh positions in ring order) between devices that
    are not torus neighbours: each crosses at least two links. 0 where
    the devices have no coordinates or the ring has one member."""
    coords = _chip_coords(devices)
    w = len(ring)
    if coords is None or w < 2:
        return 0
    return sum(not _linked(coords[ring[i]], coords[ring[(i + 1) % w]])
               for i in range(w))


def torus_ring(devices) -> tuple[int, ...]:
    """The positions of `devices` in an order whose consecutive devices,
    last and first included, are torus neighbours, found by a small
    search from position 0. The identity where the devices have no
    coordinates, the world is at most 2 or above RING_SEARCH_MAX, the
    identity already is such a cycle, or none exists."""
    devices = list(devices)
    w = len(devices)
    ident = tuple(range(w))
    coords = _chip_coords(devices)
    if (coords is None or not 2 < w <= RING_SEARCH_MAX
            or ring_detours(devices, ident) == 0):
        return ident
    links = [[j for j in range(w) if _linked(coords[i], coords[j])]
             for i in range(w)]
    path, seen = [0], {0}

    def extend() -> bool:
        if len(path) == w:
            return 0 in links[path[-1]]
        for j in links[path[-1]]:
            if j not in seen:
                path.append(j)
                seen.add(j)
                if extend():
                    return True
                path.pop()
                seen.discard(j)
        return False

    return tuple(path) if extend() else ident


def _check_ring(ring, world: int) -> tuple[int, ...]:
    ring = tuple(range(world)) if ring is None else tuple(ring)
    if sorted(ring) != list(range(world)):
        raise ValueError(f"ring {ring} is not an order of 0..{world - 1}")
    return ring


def _ring_walk(me, ring: tuple[int, ...]):
    """`me`'s position on `ring` and the LOGICAL ids of its successor and
    predecessor there. The identity ring is plain mesh index +- 1, so
    meshes that keep their own order (one chip, CPU devices) lower with
    no select chain; any other ring is read through one on the scalar."""
    world = len(ring)
    w = jnp.int32(world)
    if ring == tuple(range(world)):
        return me, lax.rem(me + 1, w), lax.rem(me + w - 1, w)
    pos = jnp.int32(0)
    nxt = jnp.int32(ring[1])
    prv = jnp.int32(ring[-1])
    for i in range(1, world):
        here = me == ring[i]
        pos = jnp.where(here, i, pos)
        nxt = jnp.where(here, ring[(i + 1) % world], nxt)
        prv = jnp.where(here, ring[i - 1], prv)
    return pos, nxt, prv


def _slot_id(slot: int, bidir: bool, world: int) -> int | None:
    """The slot's collective_id, or None at world 1: the kernel then
    takes no barrier semaphore, and Mosaic refuses a collective_id
    without one."""
    if not 0 <= slot < NUM_RING_SLOTS:
        raise ValueError(f"ring slot {slot} outside 0..{NUM_RING_SLOTS - 1}")
    if world == 1:
        return None
    return 2 * slot + (1 if bidir else 0)


def _sublane(dtype) -> int:
    """Rows of the dtype's VMEM tile (fp32 (8,128), bf16 (16,128), int8
    (32,128)). Dynamic row offsets into a VMEM ref must be provably
    tile-aligned, so per-rank chunks are padded to whole tiles."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def _kernel(axis_name, ring, chunk, func, x_ref, o_ref, v_ref, comm_ref,
            send_sem, recv_sem, credit_sem):
    # chunk arithmetic runs on ring positions; hops go to ring neighbours
    world = len(ring)
    pos, nxt, prv = _ring_walk(lax.axis_index(axis_name), ring)
    w = jnp.int32(world)
    total_hops = 2 * (world - 1)

    def combine(a, b):
        return a + b if func == ReduceFunction.SUM else jnp.maximum(a, b)

    def local_chunk(idx):
        return x_ref[pl.ds(idx * chunk, chunk)]

    # Neighbor barrier: nobody issues a remote write until its peers are in
    # the kernel (remote comm buffers alive) — the role CFGRDY + rx-ring
    # priming plays at the reference's bring-up. A world-1 ring has no
    # peers (and no hops): skip it so the degenerate kernel still
    # executes on a single attached chip.
    if world > 1:
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=nxt)
        pltpu.semaphore_signal(barrier, inc=1, device_id=prv)
        pltpu.semaphore_wait(barrier, 2)

    def hop(t):
        """One ring hop of the accumulator into the next rank's slot t%2.
        Before reusing a slot, wait for the downstream consumer's release
        credit — the rx-buffer release-on-ack protocol of the reference
        (rxbuf_seek/dma_mover.cpp:724-737), without which a fast sender
        overwrites a slot its neighbor hasn't drained."""
        slot = t % 2
        if t >= 2:
            pltpu.semaphore_wait(credit_sem.at[slot], 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=v_ref,
            dst_ref=comm_ref.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=nxt,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()
        return slot

    def release(t, slot):
        # Tell the upstream writer its slot is drained (skipped on the
        # final uses so semaphores end the call balanced).
        if t + 2 < total_hops:
            pltpu.semaphore_signal(credit_sem.at[slot], inc=1, device_id=prv)

    # ---- reduce-scatter phase: accumulator starts as our copy of chunk
    # pos-1; the hop-s arrival is the partial of chunk pos-2-s (see
    # schedules.reduce_scatter_ring_schedule for the index derivation).
    v_ref[...] = local_chunk(lax.rem(pos + w - 1, w))
    for s in range(world - 1):
        slot = hop(s)
        idx = lax.rem(pos + 2 * w - 2 - s, w)
        v_ref[...] = combine(comm_ref[slot], local_chunk(idx))
        release(s, slot)

    # ---- allgather phase: our reduced chunk is chunk `pos`; relay P-1
    # times, filing the hop-s arrival at chunk pos-1-s.
    o_ref[pl.ds(pos * chunk, chunk)] = v_ref[...]
    for s in range(world - 1):
        t = world - 1 + s
        slot = hop(t)
        origin = lax.rem(pos + 2 * w - 1 - s, w)
        v_ref[...] = comm_ref[slot]
        o_ref[pl.ds(origin * chunk, chunk)] = comm_ref[slot]
        release(t, slot)


def _compiled_f16_detour(x, interpret):
    """The v5e Mosaic dialect rejects float16 (see pallas_kernels
    ._mosaic_rejects), so a compiled-on-TPU ring over an f16 wire domain
    runs the kernel in fp32 and casts the result back: numerics are at
    least as accurate (fp32 ring accumulation, one final f16 round) at the
    cost of 2x wire bytes. Interpret-mode (CPU) f16 stays on the native
    f16 path. Returns a rerun closure, or None when no detour is needed."""
    from .pallas_kernels import _mosaic_rejects

    if not (interpret is False and _mosaic_rejects(x.dtype)):
        return None
    orig = x.dtype

    def rerun(entry, **kw):
        return entry(x.astype(jnp.float32), **kw).astype(orig)

    return rerun


def ring_allreduce_pallas(
    x,
    *,
    axis_name: str,
    world: int,
    interpret,
    func: ReduceFunction = ReduceFunction.SUM,
    slot: int = 0,
    ring: tuple[int, ...] | None = None,
):
    """Per-device body (call inside shard_map): fused ring allreduce of a
    flat (n,) buffer. Pads n up to a world-aligned, lane-aligned chunk.
    `slot` selects an independent semaphore/comm-buffer set (see
    NUM_RING_SLOTS) so segmented launches can overlap. `ring` is the
    mesh positions in the order the ring walks them (`torus_ring`); None
    walks the mesh's own order."""
    ring = _check_ring(ring, world)
    f16_detour = _compiled_f16_detour(x, interpret)
    if f16_detour is not None:
        return f16_detour(
            ring_allreduce_pallas, axis_name=axis_name, world=world,
            func=func, interpret=interpret, slot=slot, ring=ring)
    n = x.shape[-1]
    tile = _sublane(x.dtype) * 128
    chunk = -(-n // world)
    chunk = -(-chunk // tile) * tile  # whole-tile chunks (lane + sublane)
    padded = world * chunk
    if padded != n:
        x = jnp.pad(x, (0, padded - n))
    x2 = x.reshape(padded // 128, 128)
    chunk_rows = chunk // 128

    kernel = functools.partial(_kernel, axis_name, ring, chunk_rows, func)
    out = pl.pallas_call(
        kernel,
        # vma: the output varies across the collective axis (per-device
        # shards differ mid-schedule), required by shard_map's vma checking.
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype, vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((chunk_rows, 128), x2.dtype),       # accumulator
            pltpu.VMEM((2, chunk_rows, 128), x2.dtype),    # comm slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),  # slot release credits
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=_slot_id(slot, bidir=False, world=world)),
        interpret=interpret,
        name="ring_allreduce",
    )(x2)
    return out.reshape(padded)[:n]


# ---------------------------------------------------------------------------
# Bidirectional ring: both ICI link directions carry half the payload each,
# doubling effective ring bandwidth (the axis3x/bi-ring optimization the
# FPGA fabric cannot express — TPU ICI links are full-duplex in both
# neighbor directions).
# ---------------------------------------------------------------------------


def _kernel_bidir(axis_name, ring, chunk, func, x_ref, o_ref,
                  vf_ref, vb_ref, commf_ref, commb_ref,
                  sendf_sem, recvf_sem, sendb_sem, recvb_sem,
                  creditf_sem, creditb_sem):
    """Two independent ring pipelines in one kernel: rows [0, world*chunk)
    flow forward (to the ring successor), rows [world*chunk,
    2*world*chunk) flow backward (to the ring predecessor). Same RS+AG
    structure and credit protocol as the unidirectional kernel, with
    mirrored chunk indexing for the reverse direction."""
    world = len(ring)
    pos, nxt, prv = _ring_walk(lax.axis_index(axis_name), ring)
    w = jnp.int32(world)
    half = world * chunk  # rows in each direction's region
    total_hops = 2 * (world - 1)

    def combine(a, b):
        return a + b if func == ReduceFunction.SUM else jnp.maximum(a, b)

    if world > 1:  # see the unidirectional kernel's barrier note
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id=nxt)
        pltpu.semaphore_signal(barrier, inc=1, device_id=prv)
        pltpu.semaphore_wait(barrier, 2)

    def fwd_chunk(idx):
        return x_ref[pl.ds(idx * chunk, chunk)]

    def bwd_chunk(idx):
        return x_ref[pl.ds(half + idx * chunk, chunk)]

    def hop(t):
        slot = t % 2
        if t >= 2:
            pltpu.semaphore_wait(creditf_sem.at[slot], 1)
            pltpu.semaphore_wait(creditb_sem.at[slot], 1)
        rf = pltpu.make_async_remote_copy(
            src_ref=vf_ref, dst_ref=commf_ref.at[slot],
            send_sem=sendf_sem.at[slot], recv_sem=recvf_sem.at[slot],
            device_id=nxt, device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rb = pltpu.make_async_remote_copy(
            src_ref=vb_ref, dst_ref=commb_ref.at[slot],
            send_sem=sendb_sem.at[slot], recv_sem=recvb_sem.at[slot],
            device_id=prv, device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rf.start()
        rb.start()
        rf.wait()
        rb.wait()
        return slot

    def release(t, slot):
        if t + 2 < total_hops:
            pltpu.semaphore_signal(creditf_sem.at[slot], inc=1, device_id=prv)
            pltpu.semaphore_signal(creditb_sem.at[slot], inc=1, device_id=nxt)

    # RS phase. Forward direction: start chunk pos-1, step-s arrival is
    # chunk pos-2-s. Backward (mirror): start chunk pos+1, arrival pos+2+s.
    vf_ref[...] = fwd_chunk(lax.rem(pos + w - 1, w))
    vb_ref[...] = bwd_chunk(lax.rem(pos + 1, w))
    for s in range(world - 1):
        slot = hop(s)
        fidx = lax.rem(pos + 2 * w - 2 - s, w)
        bidx = lax.rem(pos + 2 + s, w)
        vf_ref[...] = combine(commf_ref[slot], fwd_chunk(fidx))
        vb_ref[...] = combine(commb_ref[slot], bwd_chunk(bidx))
        release(s, slot)

    # AG phase. Forward arrival at step s originated at pos-1-s; backward
    # at pos+1+s.
    o_ref[pl.ds(pos * chunk, chunk)] = vf_ref[...]
    o_ref[pl.ds(half + pos * chunk, chunk)] = vb_ref[...]
    for s in range(world - 1):
        t = world - 1 + s
        slot = hop(t)
        forig = lax.rem(pos + 2 * w - 1 - s, w)
        borig = lax.rem(pos + 1 + s, w)
        vf_ref[...] = commf_ref[slot]
        vb_ref[...] = commb_ref[slot]
        o_ref[pl.ds(forig * chunk, chunk)] = commf_ref[slot]
        o_ref[pl.ds(half + borig * chunk, chunk)] = commb_ref[slot]
        release(t, slot)


def ring_allreduce_pallas_bidir(
    x,
    *,
    axis_name: str,
    world: int,
    interpret,
    func: ReduceFunction = ReduceFunction.SUM,
    slot: int = 0,
    ring: tuple[int, ...] | None = None,
):
    """Bidirectional fused ring allreduce of a flat (n,) buffer. `slot`
    selects an independent semaphore/comm-buffer set (NUM_RING_SLOTS) so
    segmented launches can double-buffer instead of serializing. `ring`
    as in `ring_allreduce_pallas`."""
    ring = _check_ring(ring, world)
    f16_detour = _compiled_f16_detour(x, interpret)
    if f16_detour is not None:
        return f16_detour(
            ring_allreduce_pallas_bidir, axis_name=axis_name, world=world,
            func=func, interpret=interpret, slot=slot, ring=ring)
    n = x.shape[-1]
    # pad so n splits into 2 * world whole-tile chunks
    tile = _sublane(x.dtype) * 128
    chunk = -(-n // (2 * world))
    chunk = -(-chunk // tile) * tile
    padded = 2 * world * chunk
    if padded != n:
        x = jnp.pad(x, (0, padded - n))
    x2 = x.reshape(padded // 128, 128)
    chunk_rows = chunk // 128

    kernel = functools.partial(_kernel_bidir, axis_name, ring, chunk_rows, func)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype,
                                       vma=frozenset({axis_name})),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((chunk_rows, 128), x2.dtype),       # fwd accumulator
            pltpu.VMEM((chunk_rows, 128), x2.dtype),       # bwd accumulator
            pltpu.VMEM((2, chunk_rows, 128), x2.dtype),    # fwd comm slots
            pltpu.VMEM((2, chunk_rows, 128), x2.dtype),    # bwd comm slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=_slot_id(slot, bidir=True, world=world)),
        interpret=interpret,
        name="ring_allreduce_bidir",
    )(x2)
    return out.reshape(padded)[:n]
