"""Expert-parallel Mixture-of-Experts on the framework's alltoall.

Second model family beside the dense TP x SP x DP transformer
(transformer.py): the FFN is replaced by a top-1-routed MoE whose experts
shard over an `ep` mesh axis, with BOTH the token dispatch and the
return combine moving through the framework's own pairwise-rotation
alltoall schedule (sequencer/schedules.py:alltoall_schedule — the ACCL
alltoall, ccl_offload_control.c:2123-2218). This is the vadd_put pattern
again at a different scale point: device compute feeding straight into a
collective inside one compiled program, no host in the loop.

Routing is capacity-based top-k (fixed shapes, XLA-friendly): each token
routes to its top_k experts (k=1 keeps the raw router probability as the
gate; k>1 normalizes gates over the chosen k), each expert accepts at
most C = ceil(T * k / E * capacity_factor) pseudo-tokens per rank, and
overflow passes through on the residual stream (standard dropped-token
semantics).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..sequencer import schedules


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 128
    n_experts: int = 4       # total experts == ep axis size x experts_per_rank
    experts_per_rank: int = 1
    capacity_factor: float = 1.25
    top_k: int = 1           # experts per token (k=1: raw-prob gate;
                             # k>1: gates normalized over the chosen k)
    vocab: int = 64
    seq: int = 32
    dtype: str = "float32"


def init_moe_params(cfg: MoEConfig, key) -> dict:
    """Global parameter pytree: router replicated, experts stacked on the
    leading axis (sharded over ep)."""
    kr, ke1, ke2, kemb, kun = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    E = cfg.n_experts
    s = 0.02
    return {
        "embed": (jax.random.normal(kemb, (cfg.vocab, cfg.d_model)) * s).astype(dt),
        "router": (jax.random.normal(kr, (cfg.d_model, E)) * s).astype(dt),
        "w_up": (jax.random.normal(ke1, (E, cfg.d_model, cfg.d_ff)) * s).astype(dt),
        "w_down": (jax.random.normal(ke2, (E, cfg.d_ff, cfg.d_model)) * s).astype(dt),
        "unembed": (jax.random.normal(kun, (cfg.d_model, cfg.vocab)) * s).astype(dt),
    }


def moe_param_specs(cfg: MoEConfig) -> dict:
    return {
        "embed": P(),
        "router": P(),
        "w_up": P("ep"),
        "w_down": P("ep"),
        "unembed": P(),
    }


def place_moe_params(params, cfg: MoEConfig, mesh: Mesh):
    """Place a global MoE parameter pytree according to moe_param_specs."""
    specs = moe_param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs, is_leaf=lambda x: isinstance(x, P))


def _capacity(cfg: MoEConfig, tokens: int) -> int:
    return max(1, int(np.ceil(tokens / cfg.n_experts * cfg.capacity_factor)))


def _route(x, params, cfg: MoEConfig, C: int):
    """Top-k routing + capacity assignment for ONE rank's (T, D) tokens
    — the shared half of the dispatch math (the shard_map body and the
    facade-sequence path below both call it, so the two executions can
    never diverge). Returns (dispatch (E, C, D), safe_e, safe_c, keep,
    gate)."""
    T, D = x.shape
    E = cfg.n_experts
    k = cfg.top_k

    # top-k routing (router weights are replicated): each token becomes k
    # pseudo-tokens, token-major, so capacity positions fill in token order
    logits = x @ params["router"]                      # (T, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = lax.top_k(probs, k)                   # (T, k)
    gates = topv if k == 1 else topv / topv.sum(-1, keepdims=True)
    assign = topi.reshape(-1)                          # (T*k,)
    gate = gates.reshape(-1)
    x_rep = jnp.repeat(x, k, axis=0)                   # (T*k, D)

    # capacity assignment: position of each pseudo-token within its expert
    onehot = jax.nn.one_hot(assign, E, dtype=jnp.int32)          # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot              # (T*k, E)
    pos_in_e = pos.sum(axis=-1)                                  # (T*k,)
    keep = pos_in_e < C

    # dispatch buffer (E, C, D): slot [e, c] = the c-th token routed to e
    safe_e = jnp.where(keep, assign, 0)
    safe_c = jnp.where(keep, pos_in_e, 0)
    dispatch = jnp.zeros((E, C, D), x.dtype)
    dispatch = dispatch.at[safe_e, safe_c].add(
        jnp.where(keep[:, None], x_rep, 0.0)
    )
    return dispatch, safe_e, safe_c, keep, gate


def _combine_tokens(back, safe_e, safe_c, keep, gate, T: int, k: int,
                    D: int, dtype):
    """The gather-and-gate half of the combine: each pseudo-token reads
    its expert output slot, weights it by its gate, and the k expert
    contributions per token sum. Shared by both execution paths."""
    token_out = back[safe_e, safe_c]                   # (T*k, D)
    contrib = jnp.where(keep[:, None],
                        token_out * gate[:, None].astype(dtype), 0.0)
    return contrib.reshape(T, k, D).sum(axis=1)


def moe_ffn_local(x, params, cfg: MoEConfig, *, ep_axis: str, wire):
    """Per-rank MoE FFN body (runs inside shard_map): routes the local
    (T, D) tokens to experts across the ep axis through the framework
    alltoall, applies the rank's local experts, and alltoalls results
    back. Returns (T, D) expert outputs weighted by router probability
    (zeros for capacity-dropped tokens)."""
    T, D = x.shape
    ep_world = lax.axis_size(ep_axis)
    n_local = cfg.experts_per_rank
    E = ep_world * n_local
    assert E == cfg.n_experts, (E, cfg.n_experts)
    k = cfg.top_k
    C = _capacity(cfg, T * k)

    dispatch, safe_e, safe_c, keep, gate = _route(x, params, cfg, C)

    # dispatch alltoall: destination rank r gets experts [r*n_local, ...)
    flat = dispatch.reshape(-1)                        # (ep_world * n_local*C*D)
    routed = schedules.alltoall_schedule(
        flat, axis=ep_axis, world=ep_world, wire=wire
    )
    # (ep_world, n_local, C, D): source-rank-major blocks for MY experts
    recv = routed.reshape(ep_world, n_local, C, D)

    # local expert FFN: under in_specs P(ep) the expert stacks enter
    # shard_map already sliced to this rank's (n_local, ...) block, so
    # they are used directly — re-slicing by axis_index here would be a
    # clamped no-op that silently misroutes if the param spec changed
    w_up = params["w_up"]
    w_down = params["w_down"]
    assert w_up.shape[0] == n_local, (w_up.shape, n_local)
    h = jnp.einsum("slcd,ldf->slcf", recv, w_up)
    h = jax.nn.gelu(h)
    out = jnp.einsum("slcf,lfd->slcd", h, w_down)

    # return alltoall: send block s back to source rank s
    back = schedules.alltoall_schedule(
        out.reshape(-1), axis=ep_axis, world=ep_world, wire=wire
    ).reshape(E, C, D)

    # combine: gather each pseudo-token's slot, weight by its gate, and
    # sum each token's k expert contributions
    return _combine_tokens(back, safe_e, safe_c, keep, gate, T, k, D,
                           x.dtype)


def make_moe_forward(cfg: MoEConfig, mesh: Mesh):
    """Jitted SPMD forward: tokens (B, T) -> logits; batch over dp,
    experts over ep. One compiled program per call signature."""
    wire = schedules.Wire(None)
    pspecs = moe_param_specs(cfg)

    def body(params, tokens):
        x = params["embed"][tokens]                    # (Blocal, T, D)

        def per_seq(xi):
            return xi + moe_ffn_local(xi, params, cfg, ep_axis="ep",
                                      wire=wire)

        x = jax.vmap(per_seq)(x)
        x = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6)
        return jnp.einsum("btd,dv->btv", x, params["unembed"])

    # tokens shard over BOTH axes (true expert parallelism: every rank
    # routes a distinct batch shard); routing is per-sequence, so the
    # sharded program equals the single-device oracle exactly
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, P(("dp", "ep"))),
            out_specs=P(("dp", "ep")),
            check_vma=False,
        )
    )


def make_moe_train_step(cfg: MoEConfig, mesh: Mesh, lr: float = 1e-2):
    """SGD step with dp-mean + ep-aware gradient sync: expert-sharded
    grads stay local to their ep shard; replicated params (embed, router,
    unembed) mean-allreduce over BOTH axes through the framework ring."""
    from ..constants import ReduceFunction

    wire = schedules.Wire(None)
    pspecs = moe_param_specs(cfg)

    def loss_fn(params, tokens, targets):
        x = params["embed"][tokens]

        def per_seq(xi):
            return xi + moe_ffn_local(xi, params, cfg, ep_axis="ep",
                                      wire=wire)

        x = jax.vmap(per_seq)(x)
        x = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6)
        logits = jnp.einsum("btd,dv->btv", x, params["unembed"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return nll.mean()

    def _allreduce_mean(g, axis):
        world = lax.axis_size(axis)
        if world == 1:
            return g
        out = schedules.allreduce_ring_schedule(
            g.reshape(-1), func=ReduceFunction.SUM, axis=axis, world=world,
            wire=wire, seg_count=g.size,
        )
        return out.reshape(g.shape) / world

    def body(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        ep_world = lax.axis_size("ep")

        def sync(g, spec):
            g = _allreduce_mean(g, "dp")
            if "ep" in tuple(spec):
                # the alltoall transpose already accumulated every ep
                # shard's cotangent on the owning rank (one term per
                # shard-local loss), so after the dp mean the expert grad
                # is ep_world x the global-mean gradient: rescale
                return g / ep_world
            # replicated params: each rank's grad covers only its own
            # token shard — mean over ep completes the batch mean
            return _allreduce_mean(g, "ep")

        grads = jax.tree.map(sync, grads, pspecs)
        new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                  params, grads)
        for ax in ("dp", "ep"):
            loss = schedules.allreduce_ring_schedule(
                loss[None], func=ReduceFunction.SUM, axis=ax,
                world=lax.axis_size(ax), wire=wire, seg_count=1,
            )[0] / lax.axis_size(ax)
        return new_params, loss

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspecs, P(("dp", "ep")), P(("dp", "ep"))),
            out_specs=(pspecs, P()),
            check_vma=False,
        )
    )


# ---------------------------------------------------------------------------
# Device-resident MoE layer step: the dispatch -> expert -> combine round
# trip as ONE recorded descriptor batch (ROADMAP item 4's fused form)
# ---------------------------------------------------------------------------

# kernel-stream id the expert-FFN consumer registers under (any id in
# 1..246 works; one well-known default keeps the dryrun and the tests
# on the same endpoint)
MOE_EXPERT_STREAM = 11


@functools.lru_cache(maxsize=None)
def moe_expert_consumer(cfg: MoEConfig, capacity: int):
    """The expert-FFN stage as a RES_STREAM consumer: the dispatch
    alltoall's routed arrival — (ep_world, n_local, C, D) source-major
    blocks, flat — runs this rank's local experts BEFORE landing in the
    result buffer, so compute fuses into the same compiled program as
    the collective (the stream_put posture at MoE scale). The rank's
    expert weights arrive as its rows of the operand buffers
    `expert_operands` makes, so new weights of the same shapes run
    through the same compiled program; one consumer per (cfg, capacity)
    keeps the endpoint's identity, which the compile caches key on."""
    ep_world = cfg.n_experts // cfg.experts_per_rank
    n_local, C, D, F = cfg.experts_per_rank, capacity, cfg.d_model, cfg.d_ff

    def consumer(flat, w_up, w_down):
        # materialize the routed arrival before the expert matmuls: a
        # fused producer (the quantized wire's dequantize chain feeding
        # straight into dot_general) degrades XLA:CPU's gemm to a slow
        # fused loop — the barrier keeps the einsums on the fast path
        # without changing a bit of the math
        flat = lax.optimization_barrier(flat)
        recv = flat.reshape(ep_world, n_local, C, D)
        h = jax.nn.gelu(jnp.einsum("slcd,ldf->slcf", recv,
                                   w_up.reshape(n_local, D, F)))
        out = jnp.einsum("slcf,lfd->slcd", h, w_down.reshape(n_local, F, D))
        return out.reshape(-1).astype(flat.dtype)

    return consumer


def expert_operands(accl, w_up, w_down) -> tuple:
    """The stacked expert weights (n_experts, ...) as the consumer's
    operand buffers: row r holds rank r's experts."""
    from .transformer import rank_rows

    return tuple(rank_rows(accl, w, 0) for w in (w_up, w_down))


def make_expert_program(accl, cfg: MoEConfig, capacity: int, w_up, w_down):
    """The UNFUSED expert stage: the same per-rank expert-FFN body as
    the stream consumer, compiled as its OWN jit(shard_map) program over
    the routed buffer — the middle dispatch of the eager baseline (a
    descriptor-per-stage caller pays this seam; the fused sequence is
    exactly what removes it). Returns routed rows -> expert outputs."""
    from jax.sharding import PartitionSpec

    consumer = moe_expert_consumer(cfg, capacity)
    w_up_buf, w_down_buf = expert_operands(accl, w_up, w_down)

    def body(*rows):
        y = consumer(*(r.reshape(r.shape[-1]) for r in rows))
        return y.reshape(1, y.shape[-1])

    spec = PartitionSpec(accl.axis_name)
    prog = jax.jit(jax.shard_map(body, mesh=accl.mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False))
    return lambda routed: prog(routed, w_up_buf.device, w_down_buf.device)


def _ensure_expert_consumer(accl, cfg: MoEConfig, capacity: int, w_up,
                            w_down, stream_id: int) -> None:
    """Bind the expert-FFN consumer and its weights to the stream ONCE
    per (shape, weights): repeat calls with the same weights reuse the
    operand buffers instead of staging the weights again. The memo
    (held on the accl, weights kept alive so object ids cannot be
    reused) is keyed by STREAM ID alone, mirroring what the endpoint
    currently holds — keying by (stream, cfg, ...) would hit a stale
    entry after a DIFFERENT config overwrote the shared stream and
    silently run the wrong expert shapes/weights."""
    memo = getattr(accl, "_moe_consumer_memo", None)
    if memo is None:
        memo = {}
        accl._moe_consumer_memo = memo
    binding = (cfg, capacity, w_up, w_down)
    prev = memo.get(stream_id)
    if (prev is not None and prev[0] == binding[0]
            and prev[1] == binding[1] and prev[2] is w_up
            and prev[3] is w_down):
        return
    if prev is not None:
        for buf in prev[4]:
            accl.free_buffer(buf)
    operands = expert_operands(accl, w_up, w_down)
    memo[stream_id] = binding + (operands,)
    accl.register_stream_consumer(
        stream_id, moe_expert_consumer(cfg, capacity), operands=operands)


def run_moe_layer(accl, disp, mid, out, count: int, *,
                  stream_id: int = MOE_EXPERT_STREAM, fused: bool = True,
                  expert_fn=None, compress_dtype=None, peer_counts=(),
                  from_device: bool = False, to_device: bool = False,
                  lint: str = "error"):
    """One MoE layer step over registered facade buffers: the dispatch
    alltoall (expert FFN spliced as its RES_STREAM consumer) followed by
    the combine alltoall returning expert outputs to their source ranks.

    fused=True records BOTH steps through ``accl.sequence()`` — one
    ``jit(shard_map)`` program per layer step, one dispatch,
    signature-cached, the mid buffer threaded on-device between the
    stages. fused=False issues the SAME two descriptors eagerly (two
    dispatches; both paths compose the same schedule bodies, so their
    results are bitwise-identical at fp32 — pinned by test_moe).
    fused=False with `expert_fn` (make_expert_program) instead runs the
    fully EAGER descriptor-per-stage form — dispatch alltoall, the
    standalone expert program, combine alltoall: three dispatches, the
    pre-fusion baseline (intermediates stay on-device via
    from/to_device, so the baseline pays the dispatch seams, not
    artificial host round trips).

    `compress_dtype=DataType.int8` rides the blockwise-quantized wire on
    both legs explicitly; leaving it None defers to the device's
    ALLTOALL_COMPRESS_MIN_COUNT register (the autotuned crossover).
    `peer_counts` routes both legs through the capacity-bounded
    alltoallv (per-peer valid prefixes, overflow dropped on the wire)."""
    def leg(tgt, a, b, **kw):
        if peer_counts:
            tgt.alltoallv(a, b, count, peer_counts,
                          compress_dtype=compress_dtype, **kw)
        else:
            tgt.alltoall(a, b, count, compress_dtype=compress_dtype, **kw)

    if fused:
        seq = accl.sequence(lint=lint)
        leg(seq, disp, mid, res_stream=stream_id)
        leg(seq, mid, out)
        return seq.run(from_device=from_device, to_device=to_device)
    if expert_fn is not None:
        # descriptor-per-stage: expert outputs land back in mid
        # on-device, then the combine leg returns them to their sources
        # (intermediates ride from/to_device — the baseline pays the
        # dispatch-per-stage seams, not artificial host round trips)
        leg(accl, disp, mid, from_device=from_device, to_device=True)
        mid.device = expert_fn(mid.device)
        leg(accl, mid, out, from_device=True, to_device=to_device)
        return accl._last_request
    leg(accl, disp, mid, res_stream=stream_id, from_device=from_device,
        to_device=True)
    leg(accl, mid, out, from_device=True, to_device=to_device)
    return accl._last_request


def make_moe_layer_program(accl, disp, mid, out, count: int, *,
                           stream_id: int = MOE_EXPERT_STREAM,
                           compress_dtype=None, peer_counts=(),
                           lint: str = "error"):
    """The steady-state form of the fused layer step: record the
    dispatch -> expert -> combine batch ONCE and freeze it into a
    re-dispatchable SequenceProgram (resolve + lint + compile happen
    here; every `program.run()` afterwards is one dispatch). This is
    what a training/serving loop holds per MoE layer — ONE compiled
    program per layer step, dispatched per iteration."""
    seq = accl.sequence(lint=lint)
    if peer_counts:
        seq.alltoallv(disp, mid, count, peer_counts,
                      compress_dtype=compress_dtype, res_stream=stream_id)
        seq.alltoallv(mid, out, count, peer_counts,
                      compress_dtype=compress_dtype)
    else:
        seq.alltoall(disp, mid, count, compress_dtype=compress_dtype,
                     res_stream=stream_id)
        seq.alltoall(mid, out, count, compress_dtype=compress_dtype)
    return seq.compile()


def create_moe_layer_buffers(accl, cfg: MoEConfig, capacity: int):
    """(disp, mid, out) stacked rank buffers for `run_moe_layer`, each
    (world, E * C * D) fp32."""
    n = cfg.n_experts * capacity * cfg.d_model
    return tuple(accl.create_buffer(n, np.float32) for _ in range(3))


def moe_ffn_via_sequence(accl, x, params, cfg: MoEConfig, *,
                         buffers=None, capacity: int | None = None,
                         fused: bool = True, compress_dtype=None,
                         wire_capacity: int | None = None,
                         stream_id: int = MOE_EXPERT_STREAM):
    """The facade form of `moe_ffn_local`: per-rank routing host-side,
    then the dispatch -> expert -> combine round trip as recorded
    descriptors over `accl`'s axis (`x` is the stacked (world, T, D)
    token activations; returns the stacked FFN contributions). The
    routing and combine math is `_route`/`_combine_tokens` — the SAME
    helpers the shard_map body uses — and the alltoall legs lower the
    same schedule bodies, so at fp32 this path reproduces
    `moe_ffn_local` exactly.

    `wire_capacity` (experts_per_rank == 1 only) applies the capacity
    bound ON THE WIRE via alltoallv: the dispatch buffer keeps its full
    per-expert slots, but each peer accepts only the first
    wire_capacity token rows — tokens beyond it are dropped by the
    schedule itself (zero contribution after the gate), and every hop
    ships wire_capacity/C of the dense bytes."""
    world = accl.world
    T, D = int(x.shape[-2]), int(x.shape[-1])
    k = cfg.top_k
    C = capacity if capacity is not None else _capacity(cfg, T * k)
    E = cfg.n_experts
    count = (E // world) * C * D  # per-peer chunk elements
    peer_counts: tuple[int, ...] = ()
    if wire_capacity is not None and wire_capacity < C:
        if cfg.experts_per_rank != 1:
            raise ValueError(
                "wire_capacity needs experts_per_rank == 1 (a flat slot "
                "prefix is a token prefix only for one expert per rank)")
        peer_counts = (wire_capacity * D,) * world

    _ensure_expert_consumer(accl, cfg, C, params["w_up"],
                            params["w_down"], stream_id)
    if buffers is None:
        buffers = create_moe_layer_buffers(accl, cfg, C)
    disp, mid, out = buffers

    route = jax.jit(jax.vmap(lambda xi: _route(xi, params, cfg, C)))
    dispatch, safe_e, safe_c, keep, gate = route(jnp.asarray(x))
    disp.write(np.asarray(dispatch.reshape(world, -1), np.float32))
    run_moe_layer(accl, disp, mid, out, count, stream_id=stream_id,
                  fused=fused, compress_dtype=compress_dtype,
                  peer_counts=peer_counts)
    back = jnp.asarray(out.host).reshape(world, E, C, D)
    comb = jax.jit(jax.vmap(
        lambda b, se, sc, kp, g: _combine_tokens(b, se, sc, kp, g, T, k, D,
                                                 b.dtype)))
    return np.asarray(comb(back, safe_e, safe_c, keep, gate))


def moe_reference_forward(params, tokens, cfg: MoEConfig):
    """Single-device oracle: identical routing/capacity math, no mesh."""
    x = params["embed"][tokens]

    def per_seq(xi):
        T, D = xi.shape
        k = cfg.top_k
        E, C = cfg.n_experts, _capacity(cfg, T * k)
        logits = xi @ params["router"]
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        topv, topi = jax.lax.top_k(probs, k)
        gates = topv if k == 1 else topv / topv.sum(-1, keepdims=True)
        assign = topi.reshape(-1)
        gate = gates.reshape(-1)
        x_rep = jnp.repeat(xi, k, axis=0)
        onehot = jax.nn.one_hot(assign, E, dtype=jnp.int32)
        pos_in_e = ((jnp.cumsum(onehot, 0) - 1) * onehot).sum(-1)
        keep = pos_in_e < C
        safe_e = jnp.where(keep, assign, 0)
        safe_c = jnp.where(keep, pos_in_e, 0)
        disp = jnp.zeros((E, C, D), xi.dtype).at[safe_e, safe_c].add(
            jnp.where(keep[:, None], x_rep, 0.0))
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", disp, params["w_up"]))
        out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
        tok = out[safe_e, safe_c]
        contrib = jnp.where(keep[:, None],
                            tok * gate[:, None].astype(xi.dtype), 0.0)
        return xi + contrib.reshape(T, k, D).sum(axis=1)

    x = jax.vmap(per_seq)(x)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-6)
    return jnp.einsum("btd,dv->btv", x, params["unembed"])
