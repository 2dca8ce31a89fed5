"""The Pallas ring's order: a cycle of torus neighbours found from the
devices' coordinates (`ring_allreduce.torus_ring`), and the compiler
that reads it once from its mesh. Stand-in devices carry `coords`; the
described v5e topology is in tests/test_chip_compile.py."""

import itertools

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.ops.ring_allreduce import ring_detours, torus_ring
from accl_tpu.sequencer.lowering import AxisOnlyMesh, ScheduleCompiler


class Chip:
    """A device as the ring search reads it."""

    platform = "tpu"

    def __init__(self, *coords):
        self.coords = list(coords)


def grid(nx, ny):
    """Chips of an nx x ny slab, x fastest (the order JAX lists a host's
    chips in)."""
    return [Chip(x, y, 0) for y in range(ny) for x in range(nx)]


def is_neighbour_cycle(devices, ring):
    return (sorted(ring) == list(range(len(devices)))
            and ring_detours(devices, ring) == 0)


def test_2x2_walks_the_square():
    chips = grid(2, 2)
    assert ring_detours(chips, (0, 1, 2, 3)) == 2
    assert torus_ring(chips) == (0, 1, 3, 2)


def test_2x4_gives_a_neighbour_cycle():
    # the tray JAX's mesh_utils orders (0, 1, 2, 3, 7, 6, 5, 4)
    tray = grid(4, 2)
    assert torus_ring(tray) == (0, 1, 2, 3, 7, 6, 5, 4)
    # a v5e 2x4 host lists its chips two to a row
    host = grid(2, 4)
    ring = torus_ring(host)
    assert is_neighbour_cycle(host, ring)
    assert ring_detours(host, tuple(range(8))) > 0


def test_input_already_in_ring_order_keeps_it():
    sq = grid(2, 2)
    chips = [sq[i] for i in (0, 1, 3, 2)]
    assert torus_ring(chips) == (0, 1, 2, 3)
    rotated = [sq[i] for i in (3, 2, 0, 1)]
    assert torus_ring(rotated) == (0, 1, 2, 3)


@pytest.mark.parametrize("chips", [
    [Chip(0, x, 0) for x in range(4)],           # a 1x4 line: no cycle
    [Chip(x, y, 0) for x, y in ((0, 0), (1, 0), (0, 1))],  # an L of 3
    grid(3, 3),                                  # odd: no cycle exists
], ids=["line1x4", "L3", "grid3x3"])
def test_no_cycle_keeps_the_mesh_order(chips):
    assert torus_ring(chips) == tuple(range(len(chips)))


def test_no_coords_or_tiny_worlds_keep_the_mesh_order():
    cpu = jax.devices()[:4]
    assert not hasattr(cpu[0], "coords")
    assert torus_ring(cpu) == (0, 1, 2, 3)
    assert ring_detours(cpu, (0, 1, 2, 3)) == 0
    diag = [Chip(0, 0, 0), Chip(1, 1, 0)]
    assert torus_ring(diag) == (0, 1)
    assert torus_ring([Chip(0, 0, 0)]) == (0,)
    assert ring_detours([Chip(0, 0, 0)], (0,)) == 0


@pytest.mark.parametrize("nx,ny", [(2, 2), (2, 4), (4, 2), (4, 4), (2, 8)])
def test_every_shuffled_slab_gets_a_neighbour_cycle(nx, ny):
    rng = np.random.default_rng(nx * 10 + ny)
    chips = grid(nx, ny)
    for _ in range(5):
        order = rng.permutation(len(chips))
        shuffled = [chips[i] for i in order]
        assert is_neighbour_cycle(shuffled, torus_ring(shuffled))


def test_every_order_of_a_2x2_gets_a_neighbour_cycle():
    sq = grid(2, 2)
    for perm in itertools.permutations(range(4)):
        chips = [sq[i] for i in perm]
        ring = torus_ring(chips)
        assert is_neighbour_cycle(chips, ring)
        assert ring[0] == 0


class StandInMesh:
    """The mesh surface the compiler reads: devices and axis sizes."""

    def __init__(self, devices, axis="ccl"):
        self.devices = np.empty(len(devices), object)
        self.devices[:] = devices
        self.shape = {axis: len(devices)}


def test_compiler_reads_its_ring_from_its_mesh():
    comp = ScheduleCompiler(StandInMesh(grid(2, 2)))
    assert comp.on_tpu
    assert comp.ring_order == (0, 1, 3, 2)
    assert comp.ring_detours == 0
    # a sub-communicator's mesh gets its own order from its own devices
    sq = grid(2, 2)
    sub = ScheduleCompiler(StandInMesh([sq[0], sq[3], sq[1]]))
    assert sub.ring_order == (0, 1, 2)  # an L of three: no cycle
    assert sub.ring_detours == 1


def test_compiler_on_cpu_and_device_less_meshes_keeps_the_mesh_order():
    comp = ScheduleCompiler(Mesh(np.array(jax.devices()[:4]), ("ccl",)))
    assert (comp.ring_order, comp.ring_detours) == ((0, 1, 2, 3), 0)
    bare = ScheduleCompiler(AxisOnlyMesh("ccl", 5), "ccl",
                            use_pallas_ring=False)
    assert (bare.ring_order, bare.ring_detours) == ((0, 1, 2, 3, 4), 0)
    # a mesh whose other axis holds devices too keeps its own order
    two = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "ccl"))
    assert ScheduleCompiler(two, "ccl").ring_order == (0, 1)
