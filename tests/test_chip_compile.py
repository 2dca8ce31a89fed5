"""Compile rehearsals for the chip, with no chip attached.

The TPU compiler is installed next to the CPU backend, so a program can be
lowered and compiled for a *described* v5e topology from this CPU process.
That is where Mosaic validates what interpret mode cannot: tile alignment,
VMEM budgets, semaphore typing, DMA descriptors, collective_id. Every test
here also checks that the kernel really is in the executable
(`tpu_custom_call`): a kernel that silently fell back to interpret mode or
to the jnp path would otherwise pass.

Nothing runs, so nothing here says anything about results or times; the
execution half is `chip_smoke.py` on the chip. The topology is described
inside a fixture (never at import), and everything compiles in this
process: only one process may hold libtpu.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from accl_tpu.constants import ReduceFunction

WORLD = 4
BIG = 16 * 1024 * 1024  # one-chip kernel width: 16 Mi elements


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:WORLD]), ("ccl",))


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _x32():
    # the CPU suite runs with x64 on (conftest); Mosaic rejects i64 grid
    # bookkeeping, and the chip runs in the default 32-bit mode
    with jax.enable_x64(False):
        yield


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _ring_program(kernel_fn, mesh, dtype):
    from accl_tpu.ops.ring_allreduce import interpret_for

    interpret = interpret_for(mesh)
    assert interpret is False  # a TPU mesh always gets Mosaic

    def body(x):
        out = kernel_fn(x.reshape(x.shape[-1]), axis_name="ccl", world=WORLD,
                        func=ReduceFunction.SUM, interpret=interpret)
        return out.reshape(1, out.shape[-1])

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("ccl"),),
                               out_specs=P("ccl"), check_vma=False))
    x = jax.ShapeDtypeStruct((WORLD, 4096), dtype,
                             sharding=NamedSharding(mesh, P("ccl")))
    return fn.lower(x).compile()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("variant", ["uni", "bidir"])
def test_mosaic_compiles_ring_kernels_world4(mesh4, variant, dtype):
    """The fused ring allreduce kernels compile for a 4-chip ring, in the
    mode `interpret_for` picks for the TPU mesh.
    bfloat16 is the compressed wire domain and rides Mosaic natively;
    float16 exercises the fp32 detour (_compiled_f16_detour)."""
    from accl_tpu.ops.ring_allreduce import (
        ring_allreduce_pallas,
        ring_allreduce_pallas_bidir,
    )

    kernel = (ring_allreduce_pallas if variant == "uni"
              else ring_allreduce_pallas_bidir)
    _assert_kernel(_ring_program(kernel, mesh4, jax.numpy.dtype(dtype)))


@pytest.mark.parametrize("variant,name", [("uni", "ring_allreduce"),
                                          ("bidir", "ring_allreduce_bidir")])
def test_ring_kernels_carry_stable_names(mesh4, variant, name):
    """Each ring kernel names its custom call, so the device trace names
    the kernel's events by it, not by a numbered `tpu_custom_call`."""
    from accl_tpu.ops.ring_allreduce import (
        ring_allreduce_pallas,
        ring_allreduce_pallas_bidir,
    )

    kernel = (ring_allreduce_pallas if variant == "uni"
              else ring_allreduce_pallas_bidir)
    text = _ring_program(kernel, mesh4, jax.numpy.dtype("float32")).as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(ln.lstrip().startswith(f"%{name}.")
                         for ln in calls), calls


@pytest.mark.parametrize("case", [
    "allreduce_lax", "allreduce_pallas", "allreduce_bf16_wire",
    "allreduce_pallas_64MiB", "bcast", "alltoall", "reduce_scatter",
])
def test_production_lowering_compiles_world4(mesh4, case):
    """The PRODUCTION lowering (ScheduleCompiler output, the program
    TPUDevice dispatches) of a 256 KiB fp32 call compiles for a 4-chip
    topology (64 MiB for the segmented ring). The compiler picks the fused
    ring from the mesh's platform (use_pallas_ring=None), so the pallas
    cases must carry the kernel and the lax case must not."""
    from accl_tpu import (
        CallOptions,
        CompressionFlags,
        DataType,
        Operation,
        TuningParams,
    )
    from accl_tpu.sequencer import select_algorithm
    from accl_tpu.sequencer.lowering import ScheduleCompiler

    op = {"bcast": Operation.bcast, "alltoall": Operation.alltoall,
          "reduce_scatter": Operation.reduce_scatter}.get(
              case, Operation.allreduce)
    wire = case == "allreduce_bf16_wire"
    comp_flags = (CompressionFlags.ETH_COMPRESSED if wire
                  else CompressionFlags.NO_COMPRESSION)
    # fp32 elements: 256 KiB is one eager launch within the pallas ring
    # cap, 64 MiB runs the ring per segment
    count = (16 * 1024 * 1024 if case == "allreduce_pallas_64MiB"
             else 64 * 1024)
    opts = CallOptions(
        scenario=op, count=count, root_src_dst=0,
        function=int(ReduceFunction.SUM), data_type=DataType.float32,
        compression_flags=comp_flags,
        compress_dtype=DataType.bfloat16 if wire else DataType.none,
    )
    plan = select_algorithm(
        op, count, 4, WORLD, comp_flags,
        max_eager_size=1 << 30, eager_rx_buf_size=1 << 22,
        tuning=TuningParams.default(),
    )
    comp = ScheduleCompiler(
        mesh4, use_pallas_ring=False if case == "allreduce_lax" else None)
    assert comp.on_tpu
    per_rank = count * WORLD if op in (Operation.alltoall,
                                       Operation.reduce_scatter) else count
    x = jax.ShapeDtypeStruct((WORLD, per_rank), np.float32,
                             sharding=NamedSharding(mesh4, P("ccl")))
    text = comp.lower(opts, plan).lower(x).compile().as_text()
    if case.startswith(("allreduce_pallas", "allreduce_bf16")):
        assert "tpu_custom_call" in text
    elif case == "allreduce_lax":
        assert "tpu_custom_call" not in text


def test_ring_order_matches_jax_tray_order(topo):
    """On a v5e 2x2 the ring search picks the cycle JAX's own mesh
    builder lays the four chips in."""
    from jax.experimental import mesh_utils

    from accl_tpu.ops.ring_allreduce import ring_detours, torus_ring

    devices = list(topo.devices[:WORLD])
    jax_mesh = mesh_utils.create_device_mesh((WORLD,), devices=devices)
    jax_ring = tuple(devices.index(d) for d in jax_mesh.flat)
    assert torus_ring(devices) == jax_ring == (0, 1, 3, 2)
    assert ring_detours(devices, tuple(range(WORLD))) == 2


def test_production_lowering_embeds_torus_ring(mesh4):
    """The compiler over the chips in JAX's device order walks them as
    (0, 1, 3, 2), with no hop between unlinked chips, and its segmented
    64 MiB allreduce still compiles with Mosaic under the kernel's name."""
    from accl_tpu import CallOptions, DataType, Operation, TuningParams
    from accl_tpu.sequencer import select_algorithm
    from accl_tpu.sequencer.lowering import ScheduleCompiler

    comp = ScheduleCompiler(mesh4)
    assert comp.ring_order == (0, 1, 3, 2)
    assert comp.ring_detours == 0
    count = 16 * 1024 * 1024
    opts = CallOptions(scenario=Operation.allreduce, count=count,
                       function=int(ReduceFunction.SUM),
                       data_type=DataType.float32)
    plan = select_algorithm(Operation.allreduce, count, 4, WORLD,
                            max_eager_size=1 << 30,
                            eager_rx_buf_size=1 << 22,
                            tuning=TuningParams.default())
    x = jax.ShapeDtypeStruct((WORLD, count), np.float32,
                             sharding=NamedSharding(mesh4, P("ccl")))
    text = comp.lower(opts, plan).lower(x).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(ln.lstrip().startswith("%ring_allreduce_bidir.")
                         for ln in calls), calls


@pytest.mark.parametrize("nbytes", [256 * 1024, 64 * 1024 * 1024])
def test_production_lowering_compiles_world1(topo, nbytes):
    """A world-1 allreduce on one chip (the facade on a 1-device mesh)
    takes the fused ring, whose hop loops vanish: Mosaic refuses a
    collective_id without a barrier semaphore, so the kernel must not
    ask for one. 64 MiB runs the kernel per segment."""
    from accl_tpu import CallOptions, DataType, Operation, TuningParams
    from accl_tpu.sequencer import select_algorithm
    from accl_tpu.sequencer.lowering import ScheduleCompiler

    count = nbytes // 4
    mesh = Mesh(np.array(topo.devices[:1]), ("ccl",))
    opts = CallOptions(scenario=Operation.allreduce, count=count,
                       function=int(ReduceFunction.SUM),
                       data_type=DataType.float32)
    plan = select_algorithm(Operation.allreduce, count, 4, 1,
                            max_eager_size=1 << 30,
                            eager_rx_buf_size=1 << 22,
                            tuning=TuningParams.default())
    x = jax.ShapeDtypeStruct((1, count), np.float32,
                             sharding=NamedSharding(mesh, P("ccl")))
    fn = ScheduleCompiler(mesh).lower(opts, plan)
    _assert_kernel(fn.lower(x).compile())


def _f32(n, sharding):
    return jax.ShapeDtypeStruct((n,), np.float32, sharding=sharding)


def _quant_args(sharding):
    from accl_tpu.ops.compression import quant_num_blocks

    return (jax.ShapeDtypeStruct((BIG,), np.int8, sharding=sharding),
            _f32(quant_num_blocks(BIG), sharding))


@pytest.mark.parametrize("lane", [
    "combine_sum", "combine_max", "cast_bf16", "quantize", "dequantize",
    "dequant_combine", "dequant_combine_requant",
])
def test_one_chip_kernels_compile_16mi(one_chip, lane):
    """Each data-plane kernel compiles for one v5e chip at 16 Mi
    elements with interpret left to the target, and the executable holds
    the Mosaic call."""
    import functools

    import jax.numpy as jnp

    from accl_tpu.ops import pallas_kernels as pk

    x = _f32(BIG, one_chip)
    fn, args = {
        "combine_sum": (functools.partial(pk.combine_pallas, op="sum"),
                        (x, x)),
        "combine_max": (functools.partial(pk.combine_pallas, op="max"),
                        (x, x)),
        "cast_bf16": (functools.partial(pk.cast_pallas,
                                        to_dtype=jnp.bfloat16), (x,)),
        "quantize": (pk.quantize_pallas, (x,)),
        "dequantize": (functools.partial(pk.dequantize_pallas, n=BIG),
                       _quant_args(one_chip)),
        "dequant_combine": (pk.fused_dequant_combine_pallas,
                            (*_quant_args(one_chip), x)),
        "dequant_combine_requant": (pk.fused_dequant_combine_quant_pallas,
                                    (*_quant_args(one_chip), x)),
    }[lane]
    _assert_kernel(jax.jit(fn).lower(*args).compile())
