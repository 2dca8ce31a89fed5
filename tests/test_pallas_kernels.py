"""Pallas kernel tests (interpret mode on CPU — the kernel-testbench role
of the reference's HLS csim, e.g. kernels/plugins/reduce_ops testbenches).

The fused ring-allreduce kernel additionally runs under the TPU
interpreter's race detector, giving the schedule-level race checking the
reference gets by FIFO construction (SURVEY.md §5 'Race detection')."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec

from accl_tpu.constants import ReduceFunction
from accl_tpu.ops.pallas_kernels import (
    cast_pallas,
    combine_pallas,
    fused_combine_cast_pallas,
)
from accl_tpu.ops.ring_allreduce import interpret_for, ring_allreduce_pallas

RNG = np.random.default_rng(3)

@pytest.mark.parametrize("n", [128, 1000, 65536, 65537])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_combine_kernel(n, op):
    a = RNG.standard_normal(n).astype(np.float32)
    b = RNG.standard_normal(n).astype(np.float32)
    out = np.asarray(combine_pallas(a, b, op=op, interpret=True))
    exp = a + b if op == "sum" else np.maximum(a, b)
    np.testing.assert_allclose(out, exp, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_cast_kernel(dtype):
    x = RNG.standard_normal(5000).astype(np.float32)
    out = cast_pallas(x, dtype, interpret=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), x, rtol=1e-2,
                               atol=1e-2)
    back = cast_pallas(out, jnp.float32, interpret=True)
    assert back.dtype == jnp.float32


def test_fused_combine_cast():
    a = RNG.standard_normal(4096).astype(np.float16)
    b = RNG.standard_normal(4096).astype(np.float16)
    out = fused_combine_cast_pallas(a, b, op="sum", acc_dtype=jnp.float32,
                                    out_dtype=jnp.float16, interpret=True)
    assert out.dtype == jnp.float16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               (a.astype(np.float32) + b.astype(np.float32)),
                               rtol=1e-2, atol=1e-2)


SUM, MAX = ReduceFunction.SUM, ReduceFunction.MAX
RING8 = (0, 1, 2, 3, 7, 6, 5, 4)  # a 2x4 tray's neighbour cycle


def _ring_cases(base):
    """The mesh-order cases (ids kept as they were) plus explicit rings:
    the result is the same whatever order the ring walks the devices
    in, for SUM and MAX."""
    cases = [pytest.param(w, n, None, SUM, id=f"{w}-{n}") for w, n in base]
    for w, n, ring, func in [
        (4, base[0][1], (0, 1, 3, 2), SUM),
        (4, base[0][1], (3, 2, 1, 0), MAX),
        (4, base[0][1] - 24, (0, 2, 1, 3), MAX),
        (4, base[0][1], (0, 2, 1, 3), SUM),
        (8, base[1][1], RING8, MAX),
        (8, base[1][1] - 24, RING8, SUM),
    ]:
        label = "".join(map(str, ring))
        cases.append(pytest.param(w, n, ring, func,
                                  id=f"{w}-{n}-ring{label}-{func.name.lower()}"))
    return cases


def _expected(x, func):
    return np.tile(x.sum(0) if func == SUM else x.max(0), (x.shape[0], 1))


@pytest.mark.parametrize("world,n,ring,func", _ring_cases(
    [(4, 1024), (8, 2048), (8, 1000), (2, 256)]))
def test_ring_allreduce_kernel(world, n, ring, func):
    devs = np.array(jax.devices()[:world])
    mesh = Mesh(devs, ("ccl",))
    body = functools.partial(
        ring_allreduce_pallas, axis_name="ccl", world=world,
        func=func, interpret=interpret_for(mesh), ring=ring,
    )
    fn = jax.jit(
        jax.shard_map(
            lambda x: body(x.reshape(-1)).reshape(1, -1),
            mesh=mesh,
            in_specs=PartitionSpec("ccl"),
            out_specs=PartitionSpec("ccl"),
            check_vma=False,
        )
    )
    x = RNG.standard_normal((world, n)).astype(np.float32)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, _expected(x, func), rtol=1e-4, atol=1e-4)


def test_ring_allreduce_race_detector():
    """Run the fused kernel under the TPU interpreter's race detector —
    the framework's schedule race-checking facility."""
    world, n = 4, 512
    devs = np.array(jax.devices()[:world])
    mesh = Mesh(devs, ("ccl",))
    body = functools.partial(
        ring_allreduce_pallas, axis_name="ccl", world=world,
        func=ReduceFunction.SUM,
        interpret=interpret_for(mesh, detect_races=True),
    )
    fn = jax.jit(
        jax.shard_map(
            lambda x: body(x.reshape(-1)).reshape(1, -1),
            mesh=mesh,
            in_specs=PartitionSpec("ccl"),
            out_specs=PartitionSpec("ccl"),
            check_vma=False,
        )
    )
    x = RNG.standard_normal((world, n)).astype(np.float32)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.tile(x.sum(0), (world, 1)),
                               rtol=1e-4, atol=1e-4)


def test_pallas_ring_through_facade(mesh8):
    """Full driver path with the fused kernel enabled (the TPU default)."""
    from accl_tpu.accl import ACCL
    from accl_tpu.device.tpu_device import TPUDevice

    dev = TPUDevice(mesh8)
    dev.compiler.use_pallas_ring = True
    accl = ACCL(device=dev)
    x = RNG.standard_normal((8, 384)).astype(np.float32)
    sb = accl.create_buffer(384, data=x)
    rb = accl.create_buffer(384)
    accl.allreduce(sb, rb, 384, ReduceFunction.SUM)
    np.testing.assert_allclose(rb.host, np.tile(x.sum(0), (8, 1)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("world,n,ring,func", _ring_cases(
    [(4, 2048), (8, 4000), (2, 512)]))
def test_bidirectional_ring_allreduce(world, n, ring, func):
    """World-4 cases, explicit rings among them, run under the TPU
    interpreter's race detector."""
    from accl_tpu.ops.ring_allreduce import ring_allreduce_pallas_bidir

    devs = np.array(jax.devices()[:world])
    mesh = Mesh(devs, ("ccl",))
    body = functools.partial(
        ring_allreduce_pallas_bidir, axis_name="ccl", world=world,
        func=func, ring=ring,
        interpret=interpret_for(mesh, detect_races=(world == 4)),
    )
    fn = jax.jit(
        jax.shard_map(
            lambda x: body(x.reshape(-1)).reshape(1, -1),
            mesh=mesh,
            in_specs=PartitionSpec("ccl"),
            out_specs=PartitionSpec("ccl"),
            check_vma=False,
        )
    )
    x = RNG.standard_normal((world, n)).astype(np.float32)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, _expected(x, func), rtol=1e-4, atol=1e-4)


def test_pallas_ring_segmented_large_payload(mesh8):
    """Payloads past the VMEM ceiling run the fused kernel per segment."""
    from accl_tpu.accl import ACCL
    from accl_tpu.device.tpu_device import TPUDevice

    dev = TPUDevice(mesh8)
    dev.compiler.use_pallas_ring = True
    dev.compiler.PALLAS_RING_MAX_BYTES = 2048  # force segmentation
    accl = ACCL(device=dev)
    n = 3000  # 12 KB -> 6 segments
    x = RNG.standard_normal((8, n)).astype(np.float32)
    sb, rb = accl.create_buffer(n, data=x), accl.create_buffer(n)
    accl.allreduce(sb, rb, n, ReduceFunction.SUM)
    np.testing.assert_allclose(rb.host, np.tile(x.sum(0), (8, 1)),
                               rtol=1e-4, atol=1e-4)
