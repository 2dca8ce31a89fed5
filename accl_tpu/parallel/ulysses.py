"""Ulysses-style all-to-all sequence parallelism.

The alternative long-context strategy to ring attention: instead of
rotating K/V, one all-to-all re-shards activations from sequence-sharded
to head-sharded, attention runs with full sequence visibility per head
group, and a second all-to-all restores sequence sharding. Both
re-shardings run through the framework's own FLAT_ALLTOALL schedule
(sequencer/schedules.py:alltoall_schedule — the pairwise rotation
exchange of ccl_offload_control.c:2140-2211), the same program the MoE
dispatch rides, so every cross-device byte moves on framework schedules.
Communication is O(T*H*D/P) per device per direction — cheaper than the
ring when heads divide evenly, at the cost of head-count divisibility by
the axis size.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..sequencer import schedules


def _seq_to_heads(x, axis_name: str, world: int, wire: schedules.Wire):
    """(B, T_local, H, D) -> (B, T_global, H/P, D).

    Peer block w of the alltoall = my sequence block's head group w; the
    arrival from rank j is rank j's sequence block restricted to my head
    group, concatenated in source-rank (= sequence-block) order.
    """
    B, T, H, D = x.shape
    Hl = H // world
    blocks = x.reshape(B, T, world, Hl, D).transpose(2, 0, 1, 3, 4)
    routed = schedules.alltoall_schedule(
        blocks.reshape(-1), axis=axis_name, world=world, wire=wire
    )
    out = routed.reshape(world, B, T, Hl, D).transpose(1, 0, 2, 3, 4)
    return out.reshape(B, T * world, Hl, D)


def _heads_to_seq(x, axis_name: str, world: int, wire: schedules.Wire):
    """(B, T_global, H/P, D) -> (B, T_local, H, D).

    Peer block w = sequence block w of my head group; the arrival from
    rank j is my sequence block under head group j, so source rank order
    restores h = j*Hl + hl.
    """
    B, TG, Hl, D = x.shape
    T = TG // world
    blocks = x.reshape(B, world, T, Hl, D).transpose(1, 0, 2, 3, 4)
    routed = schedules.alltoall_schedule(
        blocks.reshape(-1), axis=axis_name, world=world, wire=wire
    )
    out = routed.reshape(world, B, T, Hl, D).transpose(1, 2, 0, 3, 4)
    return out.reshape(B, T, world * Hl, D)


def _attend_group(q, k, v, *, axis_name: str, world: int, causal: bool,
                  sm_scale: float, wire: schedules.Wire):
    """One head group's full Ulysses round trip: re-shard to
    head-sharded, attend with full sequence visibility, re-shard back.
    Heads are independent in attention, so running the groups
    separately computes what one monolithic round trip computes, to
    within a few ulps (see `ulysses_attention`)."""
    qg, kg, vg = (_seq_to_heads(t, axis_name, world, wire)
                  for t in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qg, kg).astype(jnp.float32) * sm_scale
    if causal:
        TG = qg.shape[1]
        mask = jnp.tril(jnp.ones((TG, TG), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    s = jnp.where(jnp.isfinite(s), s, -1e30)  # stable fully-masked rows
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vg.dtype), vg)
    return _heads_to_seq(out, axis_name, world, wire)


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = True,
                      sm_scale: float | None = None,
                      wire: schedules.Wire | None = None,
                      stripes: int = 1, serial: bool = False):
    """Per-device body (call inside shard_map): sequence-sharded q/k/v of
    shape (B, T_local, H, D) with H divisible by the axis size.

    `wire` configures the re-shardings' datapath: a blockwise-quantized
    Wire (the (fp32, int8) arith row) ships every alltoall hop as ONE
    packed codes+scales message (~3.94x fewer wire bytes, one
    quantization pass per chunk — the same lanes the MoE dispatch
    rides); None keeps the exact fp32 wire.

    `stripes` double-buffers the two re-sharding all-to-alls against
    the attention matmuls: the heads split into `stripes` groups (each
    still divisible by the axis size) and every group runs its own
    in-alltoall -> attention -> out-alltoall chain. The groups are
    data-independent, so XLA overlaps group i's wire with group i+1's
    matmuls. Attention is per-head, so the striped result is
    stripes=1's answer, but not bitwise: a group attends over fewer
    heads, and XLA compiles the softmax and P.V product differently at
    that shape. The two agree to within 4 ulps of the output's largest
    magnitude (pinned; at most 1.5 ulps measured on the CPU mesh).
    stripes=2 is the classic
    double buffer; pick the depth with timing.best_overlap_stripes
    when a calibration exists. serial=True order-barriers group i+1's
    inputs on group i's output — the serial dispatch->compute twin,
    bitwise the striped values, measurable A/B."""
    world = lax.axis_size(axis_name)
    B, T, H, D = q.shape
    if H % world != 0:
        raise ValueError(f"heads {H} must divide by axis size {world}")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if wire is None:
        wire = schedules.Wire(None)
    stripes = max(int(stripes), 1)
    if stripes == 1:
        return _attend_group(q, k, v, axis_name=axis_name, world=world,
                             causal=causal, sm_scale=sm_scale, wire=wire)
    if H % (world * stripes) != 0:
        raise ValueError(
            f"heads {H} must divide by axis size x stripes "
            f"({world} x {stripes})")
    hs = H // stripes
    outs = []
    prev = None
    for g in range(stripes):
        qs, ks, vs = (t[:, :, g * hs:(g + 1) * hs, :] for t in (q, k, v))
        if serial and prev is not None:
            # ALL three inputs barrier on the previous group, or the
            # twin's k/v all-to-alls would still overlap the previous
            # group's matmuls and the serial baseline would be
            # partially overlapped
            qs = schedules._ordered_after(qs, prev)
            ks = schedules._ordered_after(ks, prev)
            vs = schedules._ordered_after(vs, prev)
        out = _attend_group(qs, ks, vs, axis_name=axis_name, world=world,
                            causal=causal, sm_scale=sm_scale, wire=wire)
        outs.append(out)
        prev = out
    return jnp.concatenate(outs, axis=2)
