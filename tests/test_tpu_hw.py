"""On-chip execution tests (skipped unless ACCL_TPU_HW=1 on a TPU).

The compile half — every kernel and the production lowering compiled
for a described v5e topology — runs in tier 1 from a CPU process
(tests/test_chip_compile.py). These two tests EXECUTE the Mosaic kernels
on an attached chip:

    ACCL_TPU_HW=1 python -m pytest tests/test_tpu_hw.py -v
"""

import os

import jax
import numpy as np
import pytest

from accl_tpu.constants import ReduceFunction

pytestmark = pytest.mark.skipif(
    os.environ.get("ACCL_TPU_HW") != "1",
    reason="requires a TPU chip (run: ACCL_TPU_HW=1 pytest "
           "tests/test_tpu_hw.py)")


def _ring_program(kernel_fn, world):
    from jax.sharding import PartitionSpec as P

    def body(x):
        flat = x.reshape(x.shape[-1])
        out = kernel_fn(flat, axis_name="ccl", world=world,
                        func=ReduceFunction.SUM, interpret=False)
        return out.reshape(1, out.shape[-1])

    return body, P("ccl")


def test_combine_and_cast_execute_on_chip():
    """The reduce_ops / hp_compression lanes execute (not just compile)
    on the attached chip — the single-chip slice of the bench sweep."""
    from accl_tpu.ops.pallas_kernels import cast_pallas, combine_pallas

    rng = np.random.default_rng(0)
    a = jax.device_put(rng.standard_normal(8192).astype(np.float32))
    b = jax.device_put(rng.standard_normal(8192).astype(np.float32))
    out = np.asarray(combine_pallas(a, b, op="sum", interpret=False))
    np.testing.assert_allclose(out, np.asarray(a) + np.asarray(b), rtol=1e-6)

    # bf16 is the TPU-native half type and MUST ride the Mosaic lane
    import jax.numpy as jnp

    g = cast_pallas(a, jnp.bfloat16, interpret=False)
    np.testing.assert_allclose(np.asarray(g, dtype=np.float32),
                               np.asarray(a).astype(jnp.bfloat16)
                               .astype(np.float32), rtol=0)

    # f16 lanes route through the XLA guard on this toolchain (Mosaic has
    # no f16 type); numerics must still match exactly
    h = cast_pallas(a, np.float16, interpret=False)
    np.testing.assert_allclose(np.asarray(h),
                               np.asarray(a).astype(np.float16), rtol=0)


@pytest.mark.parametrize("variant", ["uni", "bidir"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ring_kernel_executes_world1_on_chip(variant, dtype):
    """EXECUTE (not just compile) the fused ring kernel on silicon: the
    attached chip runs it as a world-1 ring — the hop loops vanish but
    the Mosaic-compiled kernel body (VMEM scratch plumbing, dynamic
    tile-aligned chunk indexing, output assembly) runs for real, and a
    world-1 allreduce must be the identity."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from accl_tpu.ops.ring_allreduce import (
        ring_allreduce_pallas,
        ring_allreduce_pallas_bidir,
    )

    kernel = (ring_allreduce_pallas if variant == "uni"
              else ring_allreduce_pallas_bidir)
    mesh = Mesh(np.array(jax.devices()[:1]), ("ccl",))
    body, spec = _ring_program(kernel, 1)
    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                      check_vma=False)
    )
    x = np.random.default_rng(5).standard_normal((1, 5000)) \
        .astype(np.float32)
    out = np.asarray(fn(jnp.asarray(x, jnp.dtype(dtype)))
                     .astype(jnp.float32))
    tol = 1e-6 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(out, x, rtol=tol, atol=tol)
