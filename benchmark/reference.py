"""The plain reference and the comparison that decides `correct`.

Imports nothing of the program. The reference is a straightforward fp32
sum of the seeded per-rank inputs, rank after rank, computed on each chip
from a full copy of the inputs, so that no answer has to cross to the
host. The compared number is the widest gap between an answer and the
reference, as a share of the sum of the magnitudes that went into that
element: the scale on which a reordered fp32 sum rounds. A NaN reads as
inf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


def _block_gap(inputs, answer):
    """inputs: every rank's (ranks, n) operand; answer: this chip's block
    of answer rows. The widest gap of the block, as a (1,) array."""
    ref = inputs[0]
    scale = jnp.abs(inputs[0])
    for r in range(1, inputs.shape[0]):
        ref = ref + inputs[r]
        scale = scale + jnp.abs(inputs[r])
    scale = jnp.maximum(scale, jnp.finfo(jnp.float32).tiny)
    gap = jnp.abs(answer - ref[None, :]) / scale[None, :]
    gap = jnp.where(jnp.isnan(gap), jnp.inf, gap)
    return jnp.max(gap)[None]


@functools.lru_cache(maxsize=None)
def _gap_program(mesh, axis):
    return jax.jit(jax.shard_map(
        _block_gap, mesh=mesh,
        in_specs=(PartitionSpec(), PartitionSpec(axis)),
        out_specs=PartitionSpec(axis)))


class SumReference:
    """The reference for one set of (ranks, n) fp32 inputs, sharded over
    the ranks' chips: a full copy of them on every chip."""

    def __init__(self, inputs: jax.Array):
        self.sharding = inputs.sharding
        self.shape = inputs.shape
        mesh = self.sharding.mesh
        self.everywhere = jax.device_put(
            inputs, NamedSharding(mesh, PartitionSpec()))
        self.program = _gap_program(mesh, self.sharding.spec[0])

    def gap(self, answer) -> float:
        """The widest gap of any rank's answer row from the reference.
        `answer` is the (ranks, n) result where the caller reads it: a
        device array, or a host array, which is put back where the ranks
        live."""
        if tuple(answer.shape) != self.shape:
            return float("inf")  # an answer row per rank, or no answer
        answer = jax.device_put(answer, self.sharding)
        return float(jnp.max(self.program(self.everywhere, answer)))
