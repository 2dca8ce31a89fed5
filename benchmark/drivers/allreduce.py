"""Drives `ACCL.allreduce` through the facade, as a user of the library
calls it: buffers, seeded inputs, one call, its answer, the comparison.

Device buffers (the configuration's `"buffers": "device"`) ride
`from_device=True, to_device=True`, ACCL's from_fpga/to_fpga: the data
stays in HBM. Host buffers ride the facade's default, which stages the
operand in and the result out on every call.
"""

from __future__ import annotations

import numpy as np

import reference


def _key_words(seed: int) -> np.ndarray:
    """A threefry key from a seed of up to 64 bits."""
    seed %= 1 << 64
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


class Driver:
    def __init__(self, accl, config: dict, sizes: list[int], seed: int,
                 control: bool = False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from accl_tpu import DataType, ReduceFunction

        self.accl = accl
        self.device_buffers = config["buffers"] == "device"
        self.function = ReduceFunction[config["function"].upper()]
        # the lower-precision control: the facade's own compressed wire
        self.compress = (getattr(DataType, config["control_wire"])
                         if control else None)
        dtype = np.dtype(config["dtype"])
        world = accl.world
        counts = [s // dtype.itemsize for s in sizes]
        sharding = NamedSharding(accl.mesh, PartitionSpec(accl.axis_name))

        # every input and every poisoned result buffer in one jitted call
        # on the device, from the seed
        def make(key):
            keys = jax.random.split(key, len(counts))
            xs = tuple(jax.random.normal(k, (world, n), dtype)
                       for k, n in zip(keys, counts))
            nans = tuple(jnp.full((world, n), jnp.nan, dtype) for n in counts)
            return xs, nans

        key = jax.random.wrap_key_data(jnp.asarray(_key_words(seed)))
        xs, nans = jax.jit(
            make, out_shardings=((sharding,) * len(counts),) * 2)(key)
        self.inputs = dict(zip(sizes, xs))
        self.poison = dict(zip(sizes, nans))
        self.poison_host = {}
        self.bufs = {}
        for size, n in zip(sizes, counts):
            send = accl.create_buffer(n, dtype)
            recv = accl.create_buffer(n, dtype)
            if self.device_buffers:
                send.device = self.inputs[size]
            else:
                send.host = np.asarray(self.inputs[size])
                self.poison_host[size] = np.full((world, n), np.nan, dtype)
            self.bufs[size] = (send, recv, n)
        jax.block_until_ready(list(self.inputs.values()))

    def call(self, size: int) -> None:
        """One allreduce of `size` bytes per rank, returning once its
        result is ready where the caller reads it. The result buffer is
        poisoned first, so a call that leaves it unwritten reads NaN."""
        send, recv, n = self.bufs[size]
        recv.device = self.poison[size]
        if self.device_buffers:
            self.accl.allreduce(send, recv, n, self.function,
                                from_device=True, to_device=True,
                                compress_dtype=self.compress)
            recv.device.block_until_ready()
        else:
            recv.host = self.poison_host[size]
            self.accl.allreduce(send, recv, n, self.function,
                                compress_dtype=self.compress)

    def answer(self, size: int):
        """The result of the last call of `size`, where the caller reads
        it: the device array, or the host mirror."""
        recv = self.bufs[size][1]
        return recv.device if self.device_buffers else recv.host

    def free(self) -> None:
        """Drop the program's buffers; keep the inputs for the reference."""
        for send, recv, _ in self.bufs.values():
            self.accl.free_buffer(send)
            self.accl.free_buffer(recv)
        self.bufs.clear()
        self.poison.clear()
        self.poison_host.clear()

    def compare(self, kept: list[tuple[int, object]]) -> dict:
        """The widest gap of the kept answers from the reference."""
        ref = None
        worst = 0.0
        for size, ans in sorted(kept, key=lambda k: k[0]):
            if ref is None or ref.shape != self.inputs[size].shape:
                ref = None  # one size's reference in memory at a time
                ref = reference.SumReference(self.inputs[size])
            worst = max(worst, ref.gap(ans))
        return {"max_gap": worst}
