"""Buffer hierarchy: host-mirrored device buffers.

Reference semantics: driver/xrt/include/accl/buffer.hpp:32-204 — a
BaseBuffer pairs a host pointer with a device allocation, with explicit
sync_to_device/sync_from_device, slicing, a device address for call
descriptors, and backend-specific subclasses (XRTBuffer/SimBuffer/
CoyoteBuffer/DummyBuffer).

TPU mapping: the device allocation is a jax.Array laid out as a stacked
(world, n) array sharded over the collective mesh axis, so device r's
shard is rank r's buffer — the HBM analog of per-FPGA DDR buffers. Host
mirrors are numpy. Addresses are allocated from a per-context virtual
arena so descriptors, exchange-memory dumps and the native emulator agree
on buffer identity.
"""

from __future__ import annotations

import functools
import itertools

import jax
import numpy as np

from .constants import DataType, from_numpy_dtype

_addr_arena = itertools.count(0x1000_0000, 0x100_0000)


class BaseBuffer:
    """Common buffer interface (reference buffer.hpp:32-95)."""

    def __init__(self, shape, dtype, address=None):
        self.shape = tuple(shape)
        self.np_dtype = np.dtype(dtype)
        self.address = next(_addr_arena) if address is None else address
        # what a model keeps in the buffer (e.g. "mla_cache"), or None;
        # a sequence's compile span counts the bytes of each role it writes
        self.role: str | None = None

    @property
    def count(self) -> int:
        """Elements per rank (the descriptor's count field)."""
        return int(np.prod(self.shape[1:])) if len(self.shape) > 1 else self.shape[0]

    @property
    def data_type(self) -> DataType:
        return from_numpy_dtype(self.np_dtype)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape)) * self.np_dtype.itemsize

    def sync_to_device(self):
        raise NotImplementedError

    def sync_from_device(self):
        raise NotImplementedError


class TPUBuffer(BaseBuffer):
    """A (world, n) stacked rank buffer sharded over the mesh axis.

    The host mirror (`host`) is numpy; `device` is the sharded jax.Array.
    sync_to_device/sync_from_device move whole images, like the reference's
    explicit DMA syncs (buffer.hpp:60-72) — collectives can then run
    `from_fpga/to_fpga`-style without host round-trips. put_prefix and
    fetch_row move part of an image: a prefix of every row in, one
    rank's row out.
    """

    def __init__(self, host: np.ndarray, sharding, host_only: bool = False):
        super().__init__(host.shape, host.dtype)
        self.host = host
        self.sharding = sharding
        self.host_only = host_only
        self.device: jax.Array | None = None
        if not host_only:
            self.sync_to_device()

    @classmethod
    def on_device(cls, array: jax.Array, sharding) -> "TPUBuffer":
        """A buffer made on the device, with no host mirror until
        `sync_from_device` fetches one: for rank buffers too large to
        pass through the host (weights, caches)."""
        buf = cls.__new__(cls)
        BaseBuffer.__init__(buf, array.shape, array.dtype)
        buf.host = None
        buf.sharding = sharding
        buf.host_only = False
        buf.device = jax.device_put(array, sharding)
        return buf

    def sync_to_device(self):
        if self.host is not None:  # a device-made buffer keeps its array
            self.device = jax.device_put(self.host, self.sharding)
        return self

    def sync_from_device(self):
        if self.device is not None:
            self.host = np.asarray(jax.device_get(self.device))
        return self

    def put_prefix(self, rows: np.ndarray) -> int:
        """Write `rows` (world, k) over the first k elements of every
        rank row, in the host mirror and in the device image; the rest
        of both stays as it was. Only the prefix crosses to the device,
        written in place into the (donated) image. Returns the bytes
        put on the device."""
        rows = np.ascontiguousarray(rows, self.np_dtype)
        if self.host is not None:
            if not self.host.flags.writeable:  # as sync_from_device left it
                self.host = self.host.copy()
            self.host[:, :rows.shape[1]] = rows
        if self.device is None:
            return 0
        self.device = _prefix_writer(self.sharding)(
            self.device, jax.device_put(rows, self.sharding))
        return rows.nbytes

    def fetch_row(self, rank: int, count: int) -> np.ndarray:
        """The first `count` elements of rank `rank`'s device row, read
        from that rank's shard alone; the host mirror is left as it
        was."""
        for shard in self.device.addressable_shards:
            first, stop, _ = shard.index[0].indices(self.shape[0])
            if first <= rank < stop:
                data = shard.data
                if count < data.shape[1]:  # cut on the device
                    return np.asarray(data[rank - first, :count])
                return np.asarray(data)[rank - first]
        raise ValueError(f"rank {rank}'s row is on no addressable device")

    def write(self, data: np.ndarray):
        data = np.asarray(data, self.np_dtype).reshape(self.shape)
        self.host = data
        return self

    def rank_view(self, rank: int) -> np.ndarray:
        """Host view of one rank's buffer."""
        return self.host[rank]


@functools.lru_cache(maxsize=None)
def _prefix_writer(sharding):
    """image, prefix -> image with the prefix written over the start of
    every row, the image donated: one program per sharding."""
    return jax.jit(
        lambda image, prefix: jax.lax.dynamic_update_slice(
            image, prefix, (0, 0)),
        out_shardings=sharding, donate_argnums=0)


class EmuBuffer(BaseBuffer):
    """A per-rank host buffer registered with the native emulator runtime
    (reference SimBuffer, driver/xrt/include/accl/simbuffer.hpp): memory
    lives in this process, the runtime addresses it by `address`."""

    def __init__(self, host: np.ndarray, address=None):
        super().__init__((1,) + tuple(host.shape), host.dtype, address)
        self.host = host

    def sync_to_device(self):
        return self

    def sync_from_device(self):
        return self


class DummyBuffer(BaseBuffer):
    """Placeholder for unused operands (reference dummybuffer.hpp; used by
    prepare_call for absent operands, accl.cpp:1243-1268)."""

    def __init__(self):
        super().__init__((0,), np.float32, address=0)
        self.host = np.zeros((0,), np.float32)
        self.device = None

    def sync_to_device(self):
        return self

    def sync_from_device(self):
        return self
