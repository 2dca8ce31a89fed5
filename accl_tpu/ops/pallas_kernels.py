"""Pallas TPU kernels: the hardware form of the plugin layer.

The reference's plugin kernels are synthesizable HLS operating on 512-bit
AXI streams at 64 B/cycle: reduce_ops (elementwise SUM/MAX per TDEST,
kernels/plugins/reduce_ops/reduce_ops.cpp:31-107) and hp_compression
(fp32<->fp16 casts, kernels/plugins/hp_compression/hp_compression.cpp:30-60).
Here the same roles are VPU kernels written in Pallas, tiled to VMEM with a
1D grid over row blocks; they exist both as standalone entry points (so the
plugin layer is measurable in isolation, like the reference's kernel
testbenches) and fused inside the ring-allreduce kernel in ring_allreduce.py.

Compiled or interpreted is decided when the program is lowered, by the
platform it is lowered for (`_for_target`): a program lowered for a TPU
always gets Mosaic, one lowered for the CPU (the test suite's emulator
posture) gets interpret mode. The process's default backend plays no part,
so a CPU process that compiles for a described TPU topology gets Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# Row-block each kernel instance processes; 512 lanes x 8 sublanes of fp32
# comfortably under VMEM limits with double buffering.
_BLOCK_ROWS = 512
_LANES = 128


def _for_target(impl, *args, interpret):
    """`impl(*args, interpret=...)`, with interpret=None resolved at
    lowering time from the platform the program is lowered for
    (lax.platform_dependent): Mosaic for a TPU target, interpret mode
    for any other. An explicit `interpret` is passed through."""
    if interpret is not None:
        return impl(*args, interpret=interpret)
    return lax.platform_dependent(
        *args,
        tpu=functools.partial(impl, interpret=False),
        default=functools.partial(impl, interpret=True))


def _pad_rows(x, rows):
    rem = (-x.shape[0]) % rows
    if rem:
        x = jnp.pad(x, ((0, rem), (0, 0)))
    return x


def _as_tiles(x, lanes: int = _LANES):
    """Reshape a flat buffer to (rows, lanes), padding the tail. lanes
    must be a multiple of 128 (the VREG minor dim); wider rows give the
    streaming kernels larger contiguous DMA bursts per grid step."""
    n = x.shape[-1]
    rows = -(-n // lanes)
    flat = jnp.pad(x, (0, rows * lanes - n))
    return flat.reshape(rows, lanes), n


def _from_tiles(t, n):
    return t.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# reduce_ops: elementwise combine kernel
# ---------------------------------------------------------------------------


def _combine_kernel(op, a_ref, b_ref, o_ref):
    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] = jnp.add(a, b) if op == "sum" else jnp.maximum(a, b)


@functools.partial(jax.jit,
                   static_argnames=("op", "interpret", "block_rows",
                                    "lanes"))
def combine_pallas(a, b, op: str = "sum", interpret: bool | None = None,
                   block_rows: int | None = None, lanes: int | None = None):
    """Elementwise SUM/MAX over two flat buffers via Pallas (reduce_ops
    stream_add/stream_max analog, reduce_ops.cpp:31-73). float16 lanes
    route through XLA on real TPU (see _mosaic_rejects). block_rows /
    lanes set the per-grid-step VMEM tile (default _BLOCK_ROWS x
    _LANES)."""
    return _for_target(
        functools.partial(_combine, op=op, block_rows=block_rows,
                          lanes=lanes), a, b, interpret=interpret)


def _combine(a, b, *, op, interpret, block_rows, lanes):
    if not interpret and _mosaic_rejects(a.dtype, b.dtype):
        return jnp.add(a, b) if op == "sum" else jnp.maximum(a, b)
    block_rows = block_rows or _BLOCK_ROWS
    lanes = lanes or _LANES
    at, n = _as_tiles(a, lanes)
    bt, _ = _as_tiles(b, lanes)
    at = _pad_rows(at, block_rows)
    bt = _pad_rows(bt, block_rows)
    grid = (at.shape[0] // block_rows,)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, op),
        out_shape=jax.ShapeDtypeStruct(at.shape, at.dtype),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(at, bt)
    return _from_tiles(out, n)


# ---------------------------------------------------------------------------
# hp_compression: cast-compression kernel
# ---------------------------------------------------------------------------


def _cast_kernel(dtype, x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(dtype)


def _mosaic_rejects(*dtypes) -> bool:
    """The v5e Mosaic dialect has no f16 type (bf16 is the native half
    precision): compiled Pallas kernels touching float16 are rejected with
    'Unsupported type in mosaic dialect'. Measured on the live toolchain."""
    return any(jnp.dtype(d) == jnp.float16 for d in dtypes)


@functools.partial(jax.jit, static_argnames=("to_dtype", "interpret"))
def cast_pallas(x, to_dtype, interpret: bool | None = None):
    """Streaming dtype cast (hp_compression fp2hp/hp2fp analog) — one VMEM
    pass, grid over row blocks. float16 lanes route through XLA on real
    TPU (see _mosaic_rejects); the numerics are identical either way."""
    return _for_target(functools.partial(_cast, to_dtype=to_dtype), x,
                       interpret=interpret)


def _cast(x, *, to_dtype, interpret):
    if not interpret and _mosaic_rejects(x.dtype, to_dtype):
        return x.astype(to_dtype)
    xt, n = _as_tiles(x)
    xt = _pad_rows(xt, _BLOCK_ROWS)
    grid = (xt.shape[0] // _BLOCK_ROWS,)
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_cast_kernel, to_dtype),
        out_shape=jax.ShapeDtypeStruct(xt.shape, to_dtype),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
    )(xt)
    return _from_tiles(out, n)


# ---------------------------------------------------------------------------
# fused combine+cast: the compressed-reduction inner op (arith lane in the
# compressed domain with decompress-in / compress-out, the role of the
# clane segmenter + arith plugin chain in the reference datapath)
# ---------------------------------------------------------------------------


def _fused_kernel(op, acc_dtype, a_ref, b_ref, o_ref):
    a = a_ref[...].astype(acc_dtype)
    b = b_ref[...].astype(acc_dtype)
    r = jnp.add(a, b) if op == "sum" else jnp.maximum(a, b)
    o_ref[...] = r.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("op", "acc_dtype", "out_dtype", "interpret")
)
def fused_combine_cast_pallas(
    a, b, op="sum", acc_dtype=jnp.float32, out_dtype=None, interpret=None
):
    """Combine in acc_dtype, emit in out_dtype — one VMEM pass instead of
    decompress + reduce + compress round-trips through HBM. float16 wire
    domains route through XLA on real TPU (see _mosaic_rejects), where the
    same fusion happens at the HLO level."""
    return _for_target(
        functools.partial(_fused_combine_cast, op=op, acc_dtype=acc_dtype,
                          out_dtype=out_dtype or a.dtype),
        a, b, interpret=interpret)


def _fused_combine_cast(a, b, *, op, acc_dtype, out_dtype, interpret):
    if not interpret and _mosaic_rejects(a.dtype, b.dtype, acc_dtype,
                                         out_dtype):
        r = a.astype(acc_dtype) + b.astype(acc_dtype) if op == "sum" \
            else jnp.maximum(a.astype(acc_dtype), b.astype(acc_dtype))
        return r.astype(out_dtype)
    at, n = _as_tiles(a)
    bt, _ = _as_tiles(b)
    at = _pad_rows(at, _BLOCK_ROWS)
    bt = _pad_rows(bt, _BLOCK_ROWS)
    grid = (at.shape[0] // _BLOCK_ROWS,)
    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, op, acc_dtype),
        out_shape=jax.ShapeDtypeStruct(at.shape, jnp.dtype(out_dtype)),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(at, bt)
    return _from_tiles(out, n)


# ---------------------------------------------------------------------------
# blockwise int8 quantized wire (compressor lanes 4/5): quantize /
# dequantize / fused dequantize->reduce[->requantize] kernels. One scale
# block per tile row (QUANT_BLOCK_ELEMS = 256 lanes, a 2-VREG row), so
# the per-row max-abs reduction IS the block reduction and the fused ring
# step runs decode + combine + re-encode in a single VMEM pass instead of
# three HBM round-trips. Numerics are pinned to the jnp reference in
# ops/compression.py (the interpret-mode parity test), so the kernel and
# fallback paths are interchangeable bit-for-bit.
# ---------------------------------------------------------------------------

from ..constants import (  # noqa: E402
    QUANT_BLOCK_ELEMS,
    QUANT_INV_QMAX,
    QUANT_QMAX,
)
from .compression import quant_num_blocks as _quant_rows  # noqa: E402

_QUANT_BLOCK_ROWS = 256  # block rows (= scale blocks) per grid step


def _as_quant_tiles(x):
    """Flat buffer -> (rows, QUANT_BLOCK_ELEMS) with a zero-padded tail;
    rows further padded to the grid's row block."""
    n = x.shape[-1]
    rows = _quant_rows(n)
    flat = jnp.pad(x, (0, rows * QUANT_BLOCK_ELEMS - n))
    return flat.reshape(rows, QUANT_BLOCK_ELEMS), rows, n


def _encode_tiles(x):
    """The wire format's encode rule over (rows, block) fp32 tiles ->
    (int8 codes, (rows, 1) scales) — ONE definition shared by the
    quantize kernel and the fused ring step's requant tail, so the two
    kernel paths cannot fork the format."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax * QUANT_INV_QMAX  # the format's reciprocal-multiply rule
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -QUANT_QMAX, QUANT_QMAX)
    return jnp.where(scale > 0, q, 0.0).astype(jnp.int8), scale


def _quantize_kernel(x_ref, q_ref, s_ref):
    q_ref[...], s_ref[...] = _encode_tiles(x_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pallas(x, interpret: bool | None = None):
    """Blockwise int8 quantize (compressor lane 4): flat fp32 buffer ->
    (int8 codes [padded to a block multiple], fp32 per-block scales)."""
    return _for_target(_quantize, x, interpret=interpret)


def _quantize(x, *, interpret):
    xt, rows, n = _as_quant_tiles(x.astype(jnp.float32))
    xt = _pad_rows(xt, _QUANT_BLOCK_ROWS)
    grid = (xt.shape[0] // _QUANT_BLOCK_ROWS,)
    q, s = pl.pallas_call(
        _quantize_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(xt.shape, jnp.int8),
            jax.ShapeDtypeStruct((xt.shape[0], 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[pl.BlockSpec((_QUANT_BLOCK_ROWS, QUANT_BLOCK_ELEMS),
                               lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((_QUANT_BLOCK_ROWS, QUANT_BLOCK_ELEMS),
                         lambda i: (i, 0)),
            pl.BlockSpec((_QUANT_BLOCK_ROWS, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(xt)
    # the wire form keeps the payload's own length (see quantize_blockwise)
    return q[:rows].reshape(-1)[:n], s[:rows, 0]


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def dequantize_pallas(q, scales, n: int, interpret: bool | None = None):
    """Blockwise dequantize (decompressor lane 5): (codes, scales) ->
    n fp32 elements."""
    return _for_target(functools.partial(_dequantize, n=n), q, scales,
                       interpret=interpret)


def _dequantize(q, scales, *, n, interpret):
    rows = _quant_rows(n)
    qp = jnp.pad(q, (0, rows * QUANT_BLOCK_ELEMS - q.shape[-1]))
    qt = _pad_rows(qp.reshape(rows, QUANT_BLOCK_ELEMS), _QUANT_BLOCK_ROWS)
    st = _pad_rows(scales.reshape(rows, 1), _QUANT_BLOCK_ROWS)
    grid = (qt.shape[0] // _QUANT_BLOCK_ROWS,)
    out = pl.pallas_call(
        _dequantize_kernel,
        out_shape=jax.ShapeDtypeStruct(qt.shape, jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_QUANT_BLOCK_ROWS, QUANT_BLOCK_ELEMS),
                         lambda i: (i, 0)),
            pl.BlockSpec((_QUANT_BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_QUANT_BLOCK_ROWS, QUANT_BLOCK_ELEMS),
                               lambda i: (i, 0)),
        interpret=interpret,
    )(qt, st)
    return out[:rows].reshape(-1)[:n]


def _fused_dq_combine_kernel(op, requant, q_ref, s_ref, l_ref, *out_refs):
    x = q_ref[...].astype(jnp.float32) * s_ref[...]
    loc = l_ref[...].astype(jnp.float32)
    r = jnp.add(x, loc) if op == "sum" else jnp.maximum(x, loc)
    if not requant:
        out_refs[0][...] = r
        return
    out_refs[0][...], out_refs[1][...] = _encode_tiles(r)


def _fused_dq_call(q, scales, local, op: str, requant: bool,
                   interpret: bool | None):
    return _for_target(
        functools.partial(_fused_dq, op=op, requant=requant),
        q, scales, local, interpret=interpret)


def _fused_dq(q, scales, local, *, op, requant, interpret):
    n = local.shape[-1]
    rows = _quant_rows(n)
    lt = jnp.pad(local.astype(jnp.float32),
                 (0, rows * QUANT_BLOCK_ELEMS - n))
    lt = _pad_rows(lt.reshape(rows, QUANT_BLOCK_ELEMS), _QUANT_BLOCK_ROWS)
    qp = jnp.pad(q, (0, rows * QUANT_BLOCK_ELEMS - q.shape[-1]))
    qt = _pad_rows(qp.reshape(rows, QUANT_BLOCK_ELEMS), _QUANT_BLOCK_ROWS)
    st = _pad_rows(scales.reshape(-1, 1)[:rows], _QUANT_BLOCK_ROWS)
    grid = (qt.shape[0] // _QUANT_BLOCK_ROWS,)
    payload_spec = pl.BlockSpec((_QUANT_BLOCK_ROWS, QUANT_BLOCK_ELEMS),
                                lambda i: (i, 0))
    scale_spec = pl.BlockSpec((_QUANT_BLOCK_ROWS, 1), lambda i: (i, 0))
    if requant:
        out_shape = (jax.ShapeDtypeStruct(qt.shape, jnp.int8),
                     jax.ShapeDtypeStruct((qt.shape[0], 1), jnp.float32))
        out_specs = (payload_spec, scale_spec)
    else:
        out_shape = jax.ShapeDtypeStruct(qt.shape, jnp.float32)
        out_specs = payload_spec
    out = pl.pallas_call(
        functools.partial(_fused_dq_combine_kernel, op, requant),
        out_shape=out_shape,
        grid=grid,
        in_specs=[payload_spec, scale_spec, payload_spec],
        out_specs=out_specs,
        interpret=interpret,
    )(qt, st, lt)
    if requant:
        qo, so = out
        return qo[:rows].reshape(-1)[:n], so[:rows, 0]
    return out[:rows].reshape(-1)[:n].astype(local.dtype)


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def fused_dequant_combine_pallas(q, scales, local, op: str = "sum",
                                 interpret: bool | None = None):
    """Fused dequantize -> reduce: one VMEM pass from (codes, scales) +
    local fp32 operand to the fp32 accumulation (the terminal ring hop)."""
    return _fused_dq_call(q, scales, local, op, requant=False,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def fused_dequant_combine_quant_pallas(q, scales, local, op: str = "sum",
                                       interpret: bool | None = None):
    """Fused dequantize -> reduce -> requantize: the interior segmented
    ring step — accumulation stays fp32 inside the kernel while only
    (int8 payload + scales) leave for the next ppermute hop."""
    return _fused_dq_call(q, scales, local, op, requant=True,
                          interpret=interpret)
