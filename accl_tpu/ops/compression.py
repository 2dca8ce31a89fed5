"""Compression lanes: cast lanes (hp_compression analog) + blockwise
int8 quantized lanes (EQuARX-style, arxiv 2506.17615).

The reference runs three fp32<->fp16 casting kernel instances on the op0,
op1 and result lanes so payloads can cross the wire at half width
(reference: kernels/plugins/hp_compression/hp_compression.cpp:30-60,
rationale docs/overview.rst:39). On TPU the casts are VPU elementwise
converts that XLA fuses against the adjacent ICI transfer; bf16 is added
as the TPU-preferred wire format.

The quantized lanes go past the 2x cast ceiling: payloads travel as int8
codes with one fp32 scale per QUANT_BLOCK_ELEMS-element block (~3.94x
fewer wire bytes than fp32, scale overhead included). Quantization is
symmetric round-to-nearest-even onto [-127, 127]:

    scale_b = max(|x_b|) / 127          (one fp32 per block)
    q_i     = clip(round(x_i / scale_b), -127, 127)  as int8
    x'_i    = q_i * scale_b

so the per-element absolute error is bounded by scale_b / 2 =
max(|x_b|) / 254 per quantization pass (all-zero blocks encode scale 0
and decode exactly; blocks whose amax is small enough that the scale
underflows — or is flushed, XLA CPU runs FTZ — to zero encode as exact
zeros with error < amax < ~1.5e-36). The scale is defined as
amax * fp32(1/127), an explicit reciprocal multiply, so every executor
encodes bitwise-identically; the whole transform is deterministic and
quantized collectives are bitwise-reproducible.

Compressor lane numbering (referenced from ArithConfig rows):
  0: fp32 -> fp16     1: fp16 -> fp32
  2: fp32 -> bf16     3: bf16 -> fp32
  4: fp32 -> int8 blockwise quantize   5: int8 -> fp32 blockwise dequantize
"""

from __future__ import annotations

import contextlib
import functools
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

from ..arithconfig import (
    QUANT_COMPRESSOR_LANE,
    QUANT_DECOMPRESSOR_LANE,
    ArithConfig,
)
from ..constants import QUANT_BLOCK_ELEMS, QUANT_INV_QMAX, QUANT_QMAX

# -- semantic-boundary hook (analysis.semantics) ----------------------------
#
# The contribution-set certifier abstractly interprets schedule bodies at
# the jaxpr level. The blockwise quantize/dequantize math is elementwise-
# NONLINEAR (per-block amax mixes every element into the scale), so
# interpreting it primitive-by-primitive would dissolve exact per-element
# provenance. Under `semantic_boundaries()` — active ONLY while the
# certifier traces, never on a compile path — each public transform
# routes through a named jax.jit wrapper around the SAME jnp reference
# implementation, so the traced jaxpr carries one `jit` equation whose
# `name` identifies the transform (accl_sem_encode / accl_sem_decode /
# accl_sem_dequant_combine_* / accl_sem_dequant_requant_*) and the
# certifier can apply the lane's semantic rule (codes carry their
# payload's provenance) instead of descending. Off the flag, the public
# functions are byte-for-byte what they were: no extra trace boundary
# ever reaches a compiled program.

_SEM_BOUNDARY = False
_SEM_JITS: dict[tuple, Callable] = {}
# accl_sem_decode keys on the element count, so a long-lived process
# linting many distinct quantized shapes would otherwise grow this (and
# each entry's jit trace cache) without bound; trace-time wrappers are
# cheap to rebuild, so evict oldest-first past the cap
_SEM_JITS_CAP = 512


@contextlib.contextmanager
def semantic_boundaries() -> Iterator[None]:
    """Trace-time context: mark the quantized-lane transforms as named
    jaxpr boundaries for the semantic certifier's lifter."""
    global _SEM_BOUNDARY
    prev = _SEM_BOUNDARY
    _SEM_BOUNDARY = True
    try:
        yield
    finally:
        _SEM_BOUNDARY = prev


def _sem_jit(name: str, fn: Callable, *statics) -> Callable:
    """A cached jax.jit of `fn` whose jit equation is named `name`
    (the statics distinguish closures specialized per shape/dtype)."""
    key = (name, *statics)
    jitted = _SEM_JITS.get(key)
    if jitted is None:
        fn.__name__ = name
        jitted = jax.jit(fn)
        while len(_SEM_JITS) >= _SEM_JITS_CAP:
            _SEM_JITS.pop(next(iter(_SEM_JITS)))
        _SEM_JITS[key] = jitted
    return jitted

_COMPRESS_TARGET = {
    0: jnp.float16,
    2: jnp.bfloat16,
    QUANT_COMPRESSOR_LANE: jnp.int8,
}
_DECOMPRESS_TARGET = {
    1: jnp.float32,
    3: jnp.float32,
    QUANT_DECOMPRESSOR_LANE: jnp.float32,
}


def is_quantized(cfg: ArithConfig) -> bool:
    """True when cfg's wire is the blockwise int8 lane pair: payloads
    then travel as (int8 codes, per-block fp32 scales) instead of a
    plain cast, and hops must ride Wire.encode/hop/decode."""
    return cfg.compressor_lane == QUANT_COMPRESSOR_LANE


def wire_dtype(cfg: ArithConfig):
    """The dtype payloads travel in when ETH_COMPRESSED is set: the
    compressed domain of the active arithmetic configuration."""
    if cfg.compressed_elem_bytes == cfg.uncompressed_elem_bytes:
        return None  # dtype already at wire width; compression is a no-op
    return _COMPRESS_TARGET.get(cfg.compressor_lane, jnp.bfloat16)


def compress(x: jnp.ndarray, cfg: ArithConfig) -> jnp.ndarray:
    """Run the compressor lane of cfg over a payload."""
    if is_quantized(cfg):
        raise ValueError(
            "blockwise-quantized lanes carry (payload, scales) pairs; "
            "hops must go through Wire.encode/hop/decode, not compress()")
    wd = wire_dtype(cfg)
    return x if wd is None else x.astype(wd)


def decompress(x: jnp.ndarray, cfg: ArithConfig, out_dtype) -> jnp.ndarray:
    """Run the decompressor lane of cfg; the lane's target must agree with
    the caller's uncompressed dtype."""
    if is_quantized(cfg):
        raise ValueError(
            "blockwise-quantized lanes carry (payload, scales) pairs; "
            "hops must go through Wire.encode/hop/decode, not decompress()")
    target = _DECOMPRESS_TARGET.get(cfg.decompressor_lane)
    if target is not None and jnp.dtype(target) != jnp.dtype(out_dtype):
        raise ValueError(
            f"decompressor lane {cfg.decompressor_lane} yields {target}, "
            f"caller expects {out_dtype}"
        )
    return x.astype(out_dtype)


# ---------------------------------------------------------------------------
# blockwise int8 quantization core (compressor lanes 4/5)
# ---------------------------------------------------------------------------


def quant_num_blocks(n: int, block: int = QUANT_BLOCK_ELEMS) -> int:
    return -(-n // block)


def quantize_blockwise(x: jnp.ndarray, block: int = QUANT_BLOCK_ELEMS):
    """Encode a flat buffer as (int8 codes, per-block fp32 scales).

    The codes array keeps the payload's OWN length — the tail block is
    zero-padded only for the scale reduction, never on the wire, so a
    sub-block ring chunk ships `n + 4*ceil(n/block)` bytes instead of a
    rounded-up full block (which would cost MORE than fp32 below 64
    elements). Accumulation dtype is fp32 regardless of x's dtype: the
    quantized lanes only pair with fp32 payloads (ACCL406 gates anything
    else statically).
    """
    if _SEM_BOUNDARY:
        return _sem_jit("accl_sem_encode",
                        lambda y: _quantize_impl(y, block), block)(x)
    return _quantize_impl(x, block)


def _quantize_impl(x: jnp.ndarray, block: int = QUANT_BLOCK_ELEMS):
    n = x.shape[-1]
    pad = (-n) % block
    xf = x.astype(jnp.float32)
    xp = jnp.pad(xf, (0, pad)) if pad else xf
    # scale is DEFINED as amax * fp32(1/127), not amax / 127: a divide
    # by a literal is rewritten to a reciprocal multiply by some XLA
    # pipelines and not others (ULP-level drift), and the format must
    # encode identically in the jnp reference and the Mosaic kernel
    scales = jnp.max(jnp.abs(xp.reshape(-1, block)), axis=-1) \
        * QUANT_INV_QMAX
    # scale 0 (all-zero block, or an amax tiny enough that the divide
    # underflowed/flushed) encodes the block as exact zeros; the guard
    # keeps the 0/0 out of the divide without branching
    safe = jnp.where(scales > 0, scales, 1.0)
    per_elem = jnp.repeat(safe, block)[:n]
    q = jnp.clip(jnp.round(xf / per_elem), -QUANT_QMAX, QUANT_QMAX)
    live = jnp.repeat(scales > 0, block)[:n]
    return jnp.where(live, q, 0.0).astype(jnp.int8), scales


def dequantize_blockwise(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                         out_dtype=jnp.float32,
                         block: int = QUANT_BLOCK_ELEMS) -> jnp.ndarray:
    """Decode (codes, scales) back to n elements of out_dtype."""
    if _SEM_BOUNDARY:
        return _sem_jit(
            "accl_sem_decode",
            lambda qq, ss: _dequantize_impl(qq, ss, n, out_dtype, block),
            n, jnp.dtype(out_dtype).name, block)(q, scales)
    return _dequantize_impl(q, scales, n, out_dtype, block)


def _dequantize_impl(q: jnp.ndarray, scales: jnp.ndarray, n: int,
                     out_dtype=jnp.float32,
                     block: int = QUANT_BLOCK_ELEMS) -> jnp.ndarray:
    per_elem = jnp.repeat(scales, block)[: q.shape[-1]]
    x = q.astype(jnp.float32) * per_elem
    return x[:n].astype(out_dtype)


def pack_wire(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """(codes, scales) -> ONE int8 wire payload: the per-block fp32
    scales bitcast to 4 raw bytes each and appended after the codes.
    A quantized hop then crosses the wire as a SINGLE message instead
    of a payload + scale-side-channel ppermute pair — wire BYTES are
    unchanged (n + 4*ceil(n/block), the documented format), but the
    per-hop message count halves, which is where the pairwise exchange
    families were losing their fusion win. Exact: a bitcast
    round-trips bitwise."""
    if _SEM_BOUNDARY:
        return _sem_jit("accl_sem_pack", _pack_impl)(q, scales)
    return _pack_impl(q, scales)


def _pack_impl(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    import jax

    raw = jax.lax.bitcast_convert_type(scales, jnp.int8).reshape(-1)
    return jnp.concatenate([q, raw])


def unpack_wire(packed: jnp.ndarray, n: int):
    """Split a packed wire payload back into (codes, per-block fp32
    scales) for `n` payload elements — the exact inverse of
    `pack_wire`."""
    if _SEM_BOUNDARY:
        return _sem_jit("accl_sem_unpack",
                        lambda p: _unpack_impl(p, n), n)(packed)
    return _unpack_impl(packed, n)


def _unpack_impl(packed: jnp.ndarray, n: int):
    import jax

    nb = quant_num_blocks(n)
    raw = packed[n:n + 4 * nb].reshape(nb, 4)
    scales = jax.lax.bitcast_convert_type(raw, jnp.float32)
    return packed[:n], scales


def dequant_combine(q, scales, local, func_op: str):
    """Fused dequantize -> reduce: decode an incoming quantized partial
    and combine it with the local fp32 operand, accumulating in fp32
    (one VMEM pass via the pallas kernel on TPU; the jnp form is the
    identical-numerics reference everywhere else). The element count is
    local's — q decodes against the operand it combines with, on both
    datapaths."""
    if _SEM_BOUNDARY:
        return _sem_jit(
            f"accl_sem_dequant_combine_{func_op}",
            lambda qq, ss, ll: _dequant_combine_impl(qq, ss, ll, func_op),
            func_op)(q, scales, local)
    return _dequant_combine_impl(q, scales, local, func_op)


def _dequant_combine_impl(q, scales, local, func_op: str):
    if _use_quant_pallas():
        from .pallas_kernels import fused_dequant_combine_pallas

        return jax.lax.platform_dependent(
            q, scales, local,
            tpu=functools.partial(fused_dequant_combine_pallas, op=func_op,
                                  interpret=False),
            default=functools.partial(_dequant_combine_jnp,
                                      func_op=func_op))
    return _dequant_combine_jnp(q, scales, local, func_op)


def _dequant_combine_jnp(q, scales, local, func_op: str):
    x = _dequantize_impl(q, scales, local.shape[-1], jnp.float32)
    loc = local.astype(jnp.float32)
    out = jnp.add(x, loc) if func_op == "sum" else jnp.maximum(x, loc)
    return out.astype(local.dtype)


def dequant_combine_requant(q, scales, local, func_op: str):
    """The fused ring-step op: dequantize -> reduce (fp32) -> requantize,
    so only (int8 payload + scales) leave for the next hop while the
    accumulation itself never drops below fp32."""
    if _SEM_BOUNDARY:
        return _sem_jit(
            f"accl_sem_dequant_requant_{func_op}",
            lambda qq, ss, ll: _quantize_impl(
                _dequant_combine_impl(qq, ss, ll, func_op)),
            func_op)(q, scales, local)
    if _use_quant_pallas():
        from .pallas_kernels import fused_dequant_combine_quant_pallas

        return jax.lax.platform_dependent(
            q, scales, local,
            tpu=functools.partial(fused_dequant_combine_quant_pallas,
                                  op=func_op, interpret=False),
            default=lambda qq, ss, ll: quantize_blockwise(
                _dequant_combine_jnp(qq, ss, ll, func_op)))
    return quantize_blockwise(dequant_combine(q, scales, local, func_op))


def _use_quant_pallas() -> bool:
    """Route the fused quantized ring step through the Mosaic kernels:
    opt-in (ACCL_QUANT_PALLAS=1) until the kernel tier is measured on
    hardware, and only in programs lowered for a TPU (the callers'
    platform_dependent keeps the jnp form elsewhere) — the jnp form is
    numerically identical (the interpret-mode parity test pins it), so
    flipping the knob changes the datapath, not the results."""
    import os

    return os.environ.get("ACCL_QUANT_PALLAS") == "1"
