"""Quantized compression lanes: blockwise int8 wire (compressor lanes 4/5).

Round-trip and scale edge cases for the quantization core, jnp-vs-pallas
kernel parity (interpret mode — the Mosaic path shares the formula), the
fused dequantize->reduce->requantize ring step, the static wire-byte
audit (ppermute operand bytes of the lowered 16 MiB allreduce program
must shrink >= 1.9x vs fp32), and the reproducibility/rank-consistency
contracts the quantized ring schedules promise.

The documented error bound (docs/architecture.md): one quantization
pass adds at most scale_b / 2 = max|x_b| / 254 absolute error per
element; a P-rank ring allreduce quantizes a value's path at most P
times (P-1 reduce-scatter requantizations + 1 allgather encode).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from accl_tpu import (
    CallOptions,
    CompressionFlags,
    DataType,
    Operation,
    ReduceFunction,
    TuningParams,
)
from accl_tpu.arithconfig import DEFAULT_ARITH_CONFIG
from accl_tpu.constants import QUANT_BLOCK_ELEMS, QUANT_QMAX
from accl_tpu.ops.compression import (
    dequant_combine,
    dequant_combine_requant,
    dequantize_blockwise,
    is_quantized,
    quantize_blockwise,
    wire_dtype,
)
from accl_tpu.sequencer import select_algorithm
from accl_tpu.sequencer.lowering import ScheduleCompiler

Q_ROW = DEFAULT_ARITH_CONFIG[(DataType.float32, DataType.int8)]


def _roundtrip(x):
    q, s = quantize_blockwise(jnp.asarray(x))
    return np.asarray(dequantize_blockwise(q, s, x.shape[-1])), \
        np.asarray(q), np.asarray(s)


# ---------------------------------------------------------------------------
# arithconfig / lane plumbing
# ---------------------------------------------------------------------------


def test_quant_row_lanes():
    assert Q_ROW.compressor_lane == 4 and Q_ROW.decompressor_lane == 5
    assert Q_ROW.uncompressed_elem_bytes == 4
    assert Q_ROW.compressed_elem_bytes == 1
    # reductions must NOT run in the int8 code domain: a sum of codes
    # from different blocks is meaningless
    assert not Q_ROW.arith_is_compressed
    assert is_quantized(Q_ROW)
    assert jnp.dtype(wire_dtype(Q_ROW)) == jnp.int8
    # cast rows stay non-quantized
    assert not is_quantized(
        DEFAULT_ARITH_CONFIG[(DataType.float32, DataType.float16)])


# ---------------------------------------------------------------------------
# round trip + scale edge cases
# ---------------------------------------------------------------------------


def test_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = rng.standard_normal(QUANT_BLOCK_ELEMS * 5 + 17).astype(np.float32)
    dq, q, s = _roundtrip(x)
    pad = np.pad(x, (0, QUANT_BLOCK_ELEMS * 6 - x.shape[-1]))
    blocks = pad.reshape(-1, QUANT_BLOCK_ELEMS)
    amax = np.abs(blocks).max(-1)
    np.testing.assert_allclose(s, amax / QUANT_QMAX, rtol=1e-6)
    err = np.abs(dq - x).reshape(-1)
    bound = np.repeat(amax / (2 * QUANT_QMAX) * 1.001 + 1e-30,
                      QUANT_BLOCK_ELEMS)[: x.shape[-1]]
    assert (err <= bound).all()


def test_roundtrip_deterministic_bitwise():
    rng = np.random.default_rng(1)
    x = rng.standard_normal(3000).astype(np.float32)
    dq1, q1, s1 = _roundtrip(x)
    dq2, q2, s2 = _roundtrip(x)
    assert np.array_equal(q1, q2) and np.array_equal(s1, s2)
    assert np.array_equal(dq1, dq2)


def test_all_zero_block_exact():
    x = np.zeros(QUANT_BLOCK_ELEMS * 2, np.float32)
    dq, q, s = _roundtrip(x)
    assert (s == 0).all() and (q == 0).all()
    assert (dq == 0).all()  # zero blocks decode EXACTLY, not approximately


def test_negative_max_block():
    # block whose amax comes from the negative rail: the symmetric grid
    # must map it to -QUANT_QMAX exactly and keep the bound two-sided
    x = np.linspace(-8.0, 3.0, QUANT_BLOCK_ELEMS).astype(np.float32)
    dq, q, s = _roundtrip(x)
    assert s[0] == np.float32(8.0 / QUANT_QMAX)
    assert q[0] == -QUANT_QMAX and q.min() == -QUANT_QMAX
    assert np.abs(dq - x).max() <= 8.0 / (2 * QUANT_QMAX) * 1.001


def test_denormal_blocks():
    # subnormal-amax blocks: the scale either survives as a subnormal
    # (bound holds like any block) or flushes to zero (XLA CPU runs
    # FTZ/DAZ) — in the zero-scale regime the block must encode as
    # EXACT zeros with error below amax (< ~1.5e-36 by construction),
    # never NaN/Inf from the 0/0 divide the safe-scale guard dodges
    for val in (1e-39, 1e-45):
        x = np.full(QUANT_BLOCK_ELEMS, val, np.float32)
        dq, q, s = _roundtrip(x)
        assert np.isfinite(dq).all() and np.isfinite(s).all()
        if float(s[0]) > 0.0:
            assert np.abs(dq - x).max() <= float(s[0]) / 2 * 1.001
        else:
            assert (q == 0).all() and (dq == 0).all()
            assert np.abs(dq - x).max() <= np.abs(x).max()


def test_tail_padding_does_not_leak():
    # a 1-element buffer still encodes one block; the padded tail must
    # not perturb the scale or the decode width
    x = np.array([-3.5], np.float32)
    dq, q, s = _roundtrip(x)
    assert dq.shape == (1,)
    assert s.shape == (1,) and s[0] == np.float32(3.5 / QUANT_QMAX)
    assert abs(float(dq[0]) + 3.5) <= 3.5 / (2 * QUANT_QMAX) * 1.001


# ---------------------------------------------------------------------------
# fused dequantize -> reduce [-> requantize] (the ring-step op)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", ["sum", "max"])
def test_dequant_combine_matches_composition(op):
    rng = np.random.default_rng(2)
    n = QUANT_BLOCK_ELEMS * 3 + 5
    x = rng.standard_normal(n).astype(np.float32)
    local = rng.standard_normal(n).astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x))
    fused = np.asarray(dequant_combine(q, s, jnp.asarray(local), op))
    dq = np.asarray(dequantize_blockwise(q, s, n))
    ref = dq + local if op == "sum" else np.maximum(dq, local)
    np.testing.assert_array_equal(fused, ref)

    fq, fs = dequant_combine_requant(q, s, jnp.asarray(local), op)
    rq, rs = quantize_blockwise(jnp.asarray(ref))
    np.testing.assert_array_equal(np.asarray(fq), np.asarray(rq))
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(rs))


# ---------------------------------------------------------------------------
# pallas kernels (interpret mode): bitwise parity with the jnp reference
# ---------------------------------------------------------------------------


def test_quantize_pallas_parity():
    from accl_tpu.ops.pallas_kernels import dequantize_pallas, quantize_pallas

    rng = np.random.default_rng(3)
    n = QUANT_BLOCK_ELEMS * 300 + 77  # spans multiple grid steps + tail
    x = rng.standard_normal(n).astype(np.float32)
    q_ref, s_ref = quantize_blockwise(jnp.asarray(x))
    q_pl, s_pl = quantize_pallas(jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(q_pl), np.asarray(q_ref))
    np.testing.assert_array_equal(np.asarray(s_pl), np.asarray(s_ref))
    dq_ref = dequantize_blockwise(q_ref, s_ref, n)
    dq_pl = dequantize_pallas(q_pl, s_pl, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(dq_pl), np.asarray(dq_ref))


@pytest.mark.parametrize("op", ["sum", "max"])
def test_fused_kernel_parity(op):
    """The fused kernels against the jnp composition. SUM parity is
    ULP-level, not bitwise: the kernel's dequant-multiply feeds the add
    inside one jit scope, where XLA contracts mul+add into an FMA the
    eagerly-evaluated reference rounds in two steps. (The bitwise
    contracts the acceptance criteria pin — run-to-run and fused-vs-
    eager — compare identical compiled programs, so contraction cannot
    split them.) MAX has no contraction and stays exact."""
    from accl_tpu.ops.pallas_kernels import (
        fused_dequant_combine_pallas,
        fused_dequant_combine_quant_pallas,
    )

    rng = np.random.default_rng(4)
    n = QUANT_BLOCK_ELEMS * 7 + 31
    x = rng.standard_normal(n).astype(np.float32)
    local = rng.standard_normal(n).astype(np.float32)
    q, s = quantize_blockwise(jnp.asarray(x))
    ref = np.asarray(dequant_combine(q, s, jnp.asarray(local), op))
    got = np.asarray(fused_dequant_combine_pallas(
        q, s, jnp.asarray(local), op=op, interpret=True))
    if op == "max":
        np.testing.assert_array_equal(got, ref)
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)

    rq, rs = dequant_combine_requant(q, s, jnp.asarray(local), op)
    gq, gs = fused_dequant_combine_quant_pallas(
        q, s, jnp.asarray(local), op=op, interpret=True)
    # codes may flip by one step where the FMA-contracted accumulation
    # crosses a rounding boundary; the decoded values stay ULP-close
    assert np.abs(np.asarray(gq).astype(np.int32)
                  - np.asarray(rq).astype(np.int32)).max() <= 1
    np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                               rtol=1e-6, atol=0)
    dq_ref = np.asarray(dequantize_blockwise(rq, rs, n))
    dq_got = np.asarray(dequantize_blockwise(gq, gs, n))
    np.testing.assert_allclose(dq_got, dq_ref, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# lowered-program contracts: wire bytes, reproducibility, rank consistency
# ---------------------------------------------------------------------------


def _lower_allreduce(mesh, world, count, wire):
    flags = (CompressionFlags.ETH_COMPRESSED if wire != DataType.none
             else CompressionFlags.NO_COMPRESSION)
    opts = CallOptions(scenario=Operation.allreduce, count=count,
                       function=int(ReduceFunction.SUM),
                       compression_flags=flags,
                       data_type=DataType.float32, compress_dtype=wire)
    plan = select_algorithm(Operation.allreduce, count, 4, world, flags,
                            max_eager_size=1 << 30,
                            eager_rx_buf_size=1 << 22,
                            tuning=TuningParams.default(),
                            compress_dtype=wire)
    return ScheduleCompiler(mesh, use_pallas_ring=False).lower(opts, plan)


def _jaxpr_ppermute_bytes(jaxpr) -> int:
    """Sum the operand bytes of every ppermute equation in a (closed)
    jaxpr: the bytes each rank puts on the wire for one execution of
    the traced program (every cross-rank hop in the schedule layer is a
    ppermute)."""
    from jax.extend import core as jcore

    from accl_tpu.analysis.protocol import iter_ppermute_eqns

    return sum(v.aval.size * v.aval.dtype.itemsize
               for eqn in iter_ppermute_eqns(jaxpr)
               for v in eqn.invars
               if not isinstance(v, jcore.Literal))


def test_wire_bytes_16mib_reduction(mesh8):
    """The acceptance gate's static form: at a 16 MiB fp32 payload on
    the 8-device mesh, the TOTAL ppermute operand bytes of the lowered
    int8-wire ring allreduce must sit >= 1.9x below the fp32 program's
    (measured from the traced jaxpr — every cross-rank hop is a
    ppermute, scale side-channels included)."""
    world, count = 8, (16 * 1024 * 1024) // 4
    arg = jax.ShapeDtypeStruct((world, count), np.float32)
    b_fp32 = _jaxpr_ppermute_bytes(jax.make_jaxpr(
        _lower_allreduce(mesh8, world, count, DataType.none))(arg))
    b_q = _jaxpr_ppermute_bytes(jax.make_jaxpr(
        _lower_allreduce(mesh8, world, count, DataType.int8))(arg))
    assert b_fp32 > 0 and b_q > 0
    reduction = b_fp32 / b_q
    assert reduction >= 1.9, f"wire reduction {reduction:.2f}x < 1.9x"
    # and the measured ratio should track the format arithmetic:
    # 4 B/elem vs 1 B + 4/256 B/elem ~ 3.94x
    assert reduction == pytest.approx(4 / (1 + 4 / QUANT_BLOCK_ELEMS),
                                      rel=0.05)


def test_facade_rejects_quantized_wire_on_lane_less_backend(mesh8):
    """A backend without the blockwise ring kernels must fail the call
    HOST-SIDE: degrading int8 wire to a cast would silently double the
    bytes the caller sized the wire for."""
    from accl_tpu.accl import ACCL
    from accl_tpu.device.tpu_device import TPUDevice

    class LanelessDevice(TPUDevice):
        supports_quantized_wire = False

    accl = ACCL(device=LanelessDevice(mesh8))
    a = accl.create_buffer(64)
    b = accl.create_buffer(64)
    with pytest.raises(NotImplementedError, match="quantized"):
        accl.allreduce(a, b, 64, ReduceFunction.SUM,
                       compress_dtype=DataType.int8)
    # cast lanes stay available on the same backend
    accl.allreduce(a, b, 64, ReduceFunction.SUM,
                   compress_dtype=DataType.float16)


def test_native_executor_rejects_quantized_lane():
    """Raw-descriptor entry (no facade in the loop): the native data
    plane has no quantized kernel and must return COMPRESSION_ERROR for
    a compressor lane > 3 instead of reinterpreting it as a cast."""
    from accl_tpu.constants import ErrorCode
    from accl_tpu.device.emu_device import EmuWorld

    w = EmuWorld(2)
    try:
        def body(rank, r):
            row = DEFAULT_ARITH_CONFIG[(DataType.float32, DataType.int8)]
            arcfg = 0x300
            for k, word in enumerate(row.exchmem_words()):
                rank.write(arcfg + 4 * k, word)
            o = CallOptions(scenario=Operation.allreduce, count=64,
                            function=int(ReduceFunction.SUM),
                            compression_flags=CompressionFlags.ETH_COMPRESSED,
                            data_type=DataType.float32,
                            arithcfg_addr=arcfg)
            out = np.zeros(64, np.float32)
            try:
                rank.call(o, op0=np.ones(64, np.float32), res=out)
            except Exception as e:
                return getattr(e, "retcode", -1)
            return 0

        rcs = w.run(body)
    finally:
        w.close()
    for rc in rcs:
        assert rc & int(ErrorCode.COMPRESSION_ERROR), rcs


def test_lint_uses_active_arith_table():
    """ACCL406 must judge lane pairings against the table the batch will
    LOWER with: a custom table's extra row lints clean, and a table with
    the row removed is rejected even though the default table has it."""
    from accl_tpu.analysis.linter import SequenceLinter
    from accl_tpu.arithconfig import ArithConfig

    step = CallOptions(scenario=Operation.allreduce, count=64, function=0,
                       data_type=DataType.bfloat16,
                       compress_dtype=DataType.int8,
                       compression_flags=CompressionFlags.ETH_COMPRESSED,
                       addr_0=1, addr_2=2)
    extra = dict(DEFAULT_ARITH_CONFIG)
    extra[(DataType.bfloat16, DataType.int8)] = \
        ArithConfig(2, 1, 0, 4, 5, False, (10, 11))
    assert not SequenceLinter(4, arith_table=extra).lint([step])
    codes = [d.code for d in SequenceLinter(4).lint([step])]
    assert "ACCL406" in codes

    fp32_step = CallOptions(scenario=Operation.allreduce, count=64,
                            function=0, data_type=DataType.float32,
                            compress_dtype=DataType.int8,
                            compression_flags=CompressionFlags.ETH_COMPRESSED,
                            addr_0=1, addr_2=2)
    stripped = {k: v for k, v in DEFAULT_ARITH_CONFIG.items()
                if k != (DataType.float32, DataType.int8)}
    codes = [d.code
             for d in SequenceLinter(4, arith_table=stripped).lint([fp32_step])]
    assert "ACCL406" in codes


def test_quantized_allreduce_reproducible_and_rank_consistent(mesh8):
    world, count = 8, 3000
    fn = _lower_allreduce(mesh8, world, count, DataType.int8)
    x = np.random.default_rng(5).standard_normal(
        (world, count)).astype(np.float32)
    out1 = np.asarray(fn(x))
    out2 = np.asarray(fn(x))
    # bitwise-reproducible across runs
    np.testing.assert_array_equal(out1, out2)
    # every rank holds identical bytes (the allgather places its own
    # chunk through the same encode/decode round trip remote ranks see)
    for r in range(1, world):
        np.testing.assert_array_equal(out1[0], out1[r])
