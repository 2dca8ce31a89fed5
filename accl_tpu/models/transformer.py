"""Flagship demo: a TP x SP x DP transformer LM on the framework.

The model is deliberately the vadd_put pattern (reference
kernels/plugins/vadd_put/vadd_put.cpp:25-87 — device compute pushing
straight into a collective with no host round-trip) at training scale:
one shard_map program contains the forward, the ring-attention sequence
parallelism, the tensor-parallel partial-sum reductions, the backward,
and the data-parallel gradient sync — every cross-device byte moves
through the framework's own schedule bodies (sequencer/schedules.py),
and the host only dispatches the step.

Sharding layout over mesh axes (dp, sp, tp):
  - batch over dp, sequence over sp (ring attention handles cross-shard
    attention), attention heads + mlp hidden over tp;
  - parameters: qkv/o and mlp weights sharded over tp, embeddings
    replicated;
  - gradients: allreduced over dp and sp with the framework's ring
    schedule (eager segmented ring, the ACCL hot path).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import ReduceFunction
from ..sequencer import schedules
from ..parallel.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 256
    dtype: str = "float32"
    # grouped-query attention: kv heads < query heads shrink the KV cache
    # (the decode-path memory lever) and the ring-attention wire bytes;
    # None = multi-head (kv_heads == n_heads)
    n_kv_heads: int | None = None
    # rotary position embeddings; positions are GLOBAL under sequence
    # parallelism (each sp shard offsets by its rank)
    rope: bool = True
    rope_theta: float = 10000.0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads or self.n_heads
        assert self.n_heads % kv == 0, (self.n_heads, kv)
        return kv


# The flagship dense transformer at its on-chip width, one v5e chip at
# batch 8 x seq 1024: chip_smoke.py's train and serve phases.
FLAGSHIP_CONFIG = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                    n_kv_heads=4, n_layers=8, d_ff=4096,
                                    dtype="bfloat16")
FLAGSHIP_BATCH, FLAGSHIP_SEQ = 8, 1024


def init_params(cfg: TransformerConfig, key) -> dict:
    """Global (unsharded) parameter pytree; shard with shard_params."""
    keys = jax.random.split(key, 2 + cfg.n_layers)
    dt = jnp.dtype(cfg.dtype)
    scale = 0.02

    def dense(k, shape):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    params = {
        "embed": dense(keys[0], (cfg.vocab, cfg.d_model)),
        "unembed": dense(keys[1], (cfg.d_model, cfg.vocab)),
        "layers": [],
    }
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 6)
        params["layers"].append(
            {
                "wq": dense(k[0], (cfg.d_model, cfg.n_heads, cfg.head_dim)),
                "wkv": dense(k[4], (cfg.d_model, 2, cfg.kv_heads,
                                    cfg.head_dim)),
                "wo": dense(k[1], (cfg.n_heads, cfg.head_dim, cfg.d_model)),
                "w_up": dense(k[2], (cfg.d_model, cfg.d_ff)),
                "w_down": dense(k[3], (cfg.d_ff, cfg.d_model)),
                "ln1": jnp.ones((cfg.d_model,), dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
            }
        )
    return params


def param_specs(cfg: TransformerConfig) -> dict:
    """PartitionSpecs: tp shards heads/ff, everything else replicated."""
    layer = {
        "wq": P(None, "tp", None),
        "wkv": P(None, None, "tp", None),
        "wo": P("tp", None, None),
        "w_up": P(None, "tp"),
        "w_down": P("tp", None),
        "ln1": P(),
        "ln2": P(),
    }
    return {
        "embed": P(),
        "unembed": P(),
        "layers": [layer] * cfg.n_layers,
    }


def stack_layer_params(params) -> dict:
    """Convert the per-layer parameter list into stacked (n_layers, ...)
    leaves so the layer dim can shard over a `pp` mesh axis (stage i =
    layers [i*L/P, (i+1)*L/P))."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    return {"embed": params["embed"], "unembed": params["unembed"],
            "layers": stacked}


def unstack_layer_params(params, n_layers: int) -> dict:
    """Inverse of stack_layer_params: stacked (n_layers, ...) leaves back
    to the per-layer list form (checkpoint interop across mesh shapes)."""
    layers = [jax.tree.map(lambda x: x[i], params["layers"])
              for i in range(n_layers)]
    return {"embed": params["embed"], "unembed": params["unembed"],
            "layers": layers}


def pp_param_specs(cfg: TransformerConfig) -> dict:
    """PartitionSpecs for the stacked form: layer dim over pp, head/ff
    dims over tp as in param_specs, embeddings replicated."""
    layer = param_specs(cfg)["layers"][0]
    return {
        "embed": P(),
        "unembed": P(),
        "layers": {k: P("pp", *s) for k, s in layer.items()},
    }


def _pp_world(mesh: Mesh) -> int:
    return dict(mesh.shape).get("pp", 1)


def _spec_has_axis(spec, axis: str) -> bool:
    """True if a PartitionSpec shards any dimension over `axis`."""
    for part in spec:
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        if axis in parts:
            return True
    return False


def _rmsnorm(x, g):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * g


def _rope(x, pos, theta: float):
    """Rotate (B, T, H, D) by absolute positions `pos` (T,) — rotary
    embeddings in fp32, half-split form. Positions must be GLOBAL: under
    sequence parallelism the caller offsets by its sp shard."""
    D = x.shape[-1]
    assert D % 2 == 0, "rope needs an even head_dim"
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (T, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def _qkv(h, lyr, cfg: TransformerConfig, pos):
    """Project q / k / v with grouped-query layout and rotate q,k by the
    global positions `pos`. k/v stay at kv_heads (GQA): ring_attention
    attends grouped natively, so each sp ring hop carries the Hkv slice —
    a kv_heads/n_heads wire-byte saving per hop. Head dims are tp-LOCAL
    here, and H_local / Hkv_local == n_heads / kv_heads on every shard
    (tp must divide kv_heads)."""
    q = jnp.einsum("btd,dhk->bthk", h, lyr["wq"])
    kv = jnp.einsum("btd,dchk->btchk", h, lyr["wkv"])
    k, v = kv[:, :, 0], kv[:, :, 1]
    if cfg.rope:
        q = _rope(q, pos, cfg.rope_theta)
        k = _rope(k, pos, cfg.rope_theta)
    return q, k, v


def _tp_allreduce(x, wire, axis: str | None = "tp"):
    """Tensor-parallel partial-sum reduction through the framework's ring
    reduce-scatter + allgather schedule (the ACCL eager allreduce).
    axis=None is the single-shard degenerate (no tp axis in the mesh —
    the facade train step's data-parallel body): identity."""
    if axis is None:
        return x
    shape = x.shape
    flat = x.reshape(-1)
    out = schedules.allreduce_ring_schedule(
        flat,
        func=ReduceFunction.SUM,
        axis=axis,
        world=lax.axis_size(axis),
        wire=wire,
        seg_count=flat.shape[0],
    )
    return out.reshape(shape)


def _grad_allreduce(g, axis, wire):
    world = lax.axis_size(axis)
    if world == 1:
        return g
    shape = g.shape
    out = schedules.allreduce_ring_schedule(
        g.reshape(-1),
        func=ReduceFunction.SUM,
        axis=axis,
        world=world,
        wire=wire,
        seg_count=g.size,
    )
    return out.reshape(shape) / world  # mean over replicas


def _local_attention(q, k, v):
    """Plain causal attention over a fully-local sequence — the
    sp-axis-free degenerate of ring attention, grouped-query aware
    (the facade train step's body runs it: its mesh has only the
    collective axis, so the sequence is never sharded)."""
    B, T, H, Dh = q.shape
    kv_heads = k.shape[2]
    groups = H // kv_heads
    qg = q.reshape(B, T, kv_heads, groups, Dh)
    s = jnp.einsum("bthgk,bshk->bhgts", qg, k).astype(jnp.float32)
    s = s / np.sqrt(Dh)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhgts,bshk->bthgk", p.astype(v.dtype), v)
    return ctx.reshape(B, T, H, Dh)


# per-layer leaf order of the flat gradient/parameter vector, REVERSE
# backward-materialization order within a block: the backward produces
# the MLP's grads before the attention's, so the flat layout (unembed,
# layers N-1..0 each in this order, embed) puts the earliest-available
# gradients first — stripe 0 of an overlapped sync is ready while the
# rest of the backward still computes
_LAYER_BWD_ORDER = ("w_down", "w_up", "ln2", "wo", "wkv", "wq", "ln1")


def _backward_ordered_leaves(tree: dict) -> list:
    """The parameter/gradient leaves of the (pp=1) transformer pytree in
    backward-materialization order (see _LAYER_BWD_ORDER)."""
    leaves = [tree["unembed"]]
    for lyr in reversed(tree["layers"]):
        leaves.extend(lyr[k] for k in _LAYER_BWD_ORDER)
    leaves.append(tree["embed"])
    return leaves


def _striped_grad_sync(grads: dict, pspecs: dict, wire,
                       stripes: int, serial: bool):
    """Bucketed gradient sync, the stripe-overlapped form: per-leaf tp
    treatment first (the rescale-vs-allreduce logic is per spec), then
    ONE flat dp+sp mean-allreduce over the concatenated gradient
    vector split into `stripes` independent stripe chains. Leaves
    concatenate in backward-materialization order, and each stripe's
    ring chains depend only on its own leaves (XLA's slice-of-concat
    simplification restores the fine-grained dependence), so stripe
    i's allreduce runs while stripe i+1's gradients materialize in the
    backward. serial=True is the dispatch->compute twin: stripe 0 is
    order-barriered on the WHOLE gradient vector and each later stripe
    on its predecessor's output — bitwise-identical (barriers change
    scheduling, never values), measured as the A/B baseline."""
    tp_world = lax.axis_size("tp")

    def tp_fix(g, spec):
        if tp_world > 1:
            if _spec_has_axis(spec, "tp"):
                return g / tp_world
            return _grad_allreduce(g, "tp", wire)
        return g

    grads = jax.tree.map(tp_fix, grads, pspecs)
    leaves = _backward_ordered_leaves(grads)
    shapes = [g.shape for g in leaves]
    flat = jnp.concatenate([g.reshape(-1) for g in leaves])
    n = flat.shape[-1]
    per = -(-n // max(stripes, 1))
    outs = []
    prev = None
    for s in range(max(stripes, 1)):
        lo = s * per
        if lo >= n:
            break
        seg = flat[lo:min(lo + per, n)]
        if serial:
            seg = schedules._ordered_after(
                seg, flat if prev is None else prev)
        for ax in ("dp", "sp"):
            world = lax.axis_size(ax)
            if world == 1:
                continue
            seg = schedules.allreduce_ring_schedule(
                seg, func=ReduceFunction.SUM, axis=ax, world=world,
                wire=wire, seg_count=seg.shape[-1],
            ) / world
        outs.append(seg)
        prev = outs[-1]
    flat = jnp.concatenate(outs) if len(outs) > 1 else outs[0]
    parts = []
    off = 0
    for sh in shapes:
        size = int(np.prod(sh)) if sh else 1
        parts.append(flat[off:off + size].reshape(sh))
        off += size
    out = {"unembed": parts[0], "embed": parts[-1], "layers": []}
    idx = 1
    rev_layers = []
    for _ in grads["layers"]:
        lyr = {}
        for k in _LAYER_BWD_ORDER:
            lyr[k] = parts[idx]
            idx += 1
        rev_layers.append(lyr)
    out["layers"] = list(reversed(rev_layers))
    return out


def _mlp_half(x, lyr, wire, tp_axis: str | None = "tp"):
    """ln2 + gelu MLP + tp partial-sum residual — shared by the training
    block and the decode block so the two cannot silently diverge."""
    h = _rmsnorm(x, lyr["ln2"])
    up = jax.nn.gelu(jnp.einsum("btd,df->btf", h, lyr["w_up"]))
    down_partial = jnp.einsum("btf,fd->btd", up, lyr["w_down"])
    return x + _tp_allreduce(down_partial, wire, tp_axis)


def _block(x, lyr, cfg: TransformerConfig, wire,
           tp_axis: str | None = "tp", sp_axis: str | None = "sp"):
    """One transformer block (ring attention over sp, tp partial-sum
    reductions through the framework ring). RoPE positions are global:
    each sp shard offsets by its rank. tp_axis/sp_axis None run the
    axis-free degenerates (local causal attention, identity partial
    sum) — the SAME block serving the facade train step's
    data-parallel body, so the two model forms cannot diverge."""
    h = _rmsnorm(x, lyr["ln1"])
    T = h.shape[1]
    if sp_axis is None:
        pos = jnp.arange(T)
    else:
        pos = lax.axis_index(sp_axis) * T + jnp.arange(T)
    q, k, v = _qkv(h, lyr, cfg, pos)
    if sp_axis is None:
        attn = _local_attention(q, k, v)
    else:
        attn = ring_attention(q, k, v, axis_name=sp_axis, causal=True)
    o_partial = jnp.einsum("bthk,hkd->btd", attn, lyr["wo"])
    # heads are sharded over tp: partial sums reduce on-device-ring
    x = x + _tp_allreduce(o_partial, wire, tp_axis)
    return _mlp_half(x, lyr, wire, tp_axis)


def _block_fn(cfg: TransformerConfig, wire, remat: bool):
    """The per-layer body, optionally rematerialized: jax.checkpoint drops
    the block's activations (attention scores, MLP hidden) in the forward
    pass and recomputes them — including the ring/tp collectives — during
    the backward, trading FLOPs for HBM (the long-context lever on TPU)."""
    fn = lambda x, lyr: _block(x, lyr, cfg, wire)  # noqa: E731
    return jax.checkpoint(fn) if remat else fn


def _forward_local(params, tokens, cfg: TransformerConfig, wire,
                   remat: bool = False):
    """Per-device forward: tokens (B_local, T_local) -> logits. Runs inside
    shard_map; heads are the tp-local slice, sequence the sp-local shard."""
    blk = _block_fn(cfg, wire, remat)
    x = params["embed"][tokens]  # (B, T, Dm)
    for lyr in params["layers"]:
        x = blk(x, lyr)
    x = _rmsnorm(x, jnp.ones((cfg.d_model,), x.dtype))
    return jnp.einsum("btd,dv->btv", x, params["unembed"])


def _forward_local_pp(params, tokens, cfg: TransformerConfig, wire,
                      n_microbatches: int, remat: bool = False):
    """Pipelined per-device forward: params["layers"] leaves arrive as the
    pp-local (L_local, ...) stage slice; microbatches flow through the
    GPipe schedule (parallel/pipeline.py) with each stage scanning its
    local layers, and the last stage's activations come back replicated
    for the (pp-replicated) unembed projection."""
    from ..parallel.pipeline import gpipe_schedule

    x = params["embed"][tokens]  # (B, T, Dm)
    B = x.shape[0]
    M = n_microbatches
    assert B % M == 0, (B, M)
    mb = x.reshape((M, B // M) + x.shape[1:])

    blk = _block_fn(cfg, wire, remat)

    def stage(h):
        def one_layer(carry, lyr):
            return blk(carry, lyr), None

        h, _ = lax.scan(one_layer, h, params["layers"])
        return h

    out = gpipe_schedule(mb, stage, axis="pp", world=lax.axis_size("pp"),
                         wire=wire)
    x = out.reshape(x.shape)
    x = _rmsnorm(x, jnp.ones((cfg.d_model,), x.dtype))
    return jnp.einsum("btd,dv->btv", x, params["unembed"])


def make_forward(cfg: TransformerConfig, mesh: Mesh,
                 n_microbatches: int | None = None):
    """Jitted SPMD forward: tokens (B, T) -> logits, batch over dp,
    sequence over sp, heads over tp; with a `pp` mesh axis the layer
    stack pipelines over it (params in the stacked form, see
    stack_layer_params)."""
    wire = schedules.Wire(None)
    pp = _pp_world(mesh)

    if pp > 1:
        M = n_microbatches or pp
        pspecs = pp_param_specs(cfg)

        def body(params, tokens):
            return _forward_local_pp(params, tokens, cfg, wire, M)
    else:
        pspecs = param_specs(cfg)

        def body(params, tokens):
            return _forward_local(params, tokens, cfg, wire)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(pspecs, P("dp", "sp")),
            out_specs=P("dp", "sp"),
            check_vma=False,
        )
    )


# KV-cache layout: (batch over dp, seq, heads over tp, head_dim) — ONE
# constant shared by allocation and the decode step's shard_map specs
_KV_SPEC = P("dp", None, "tp", None)


def init_kv_cache(cfg: TransformerConfig, mesh: Mesh, batch: int,
                  max_len: int):
    """Per-layer KV cache for incremental decode, sharded batch over dp
    and heads over tp (the sequence dim is NOT sharded: decode emits one
    token at a time, so sp must be 1 on the decode mesh)."""
    dt = jnp.dtype(cfg.dtype)
    sh = NamedSharding(mesh, _KV_SPEC)
    # kv_heads (not n_heads): under GQA the cache is the grouped slice —
    # the inference memory saving that motivates grouped-query attention
    shape = (batch, max_len, cfg.kv_heads, cfg.head_dim)
    return [
        {"k": jax.device_put(jnp.zeros(shape, dt), sh),
         "v": jax.device_put(jnp.zeros(shape, dt), sh)}
        for _ in range(cfg.n_layers)
    ]


def _decode_block(x, lyr, cfg, ck, cv, pos, wire):
    """One block for a single new token position: append this position's
    (rotated, grouped) k/v to the cache and attend over cache[:pos+1]
    (masked full-length dot — static shapes, so one compiled program
    serves every step). The cache holds kv_heads; query heads index their
    group's slice at attention time."""
    h = _rmsnorm(x, lyr["ln1"])
    q = jnp.einsum("btd,dhk->bthk", h, lyr["wq"])
    kv = jnp.einsum("btd,dchk->btchk", h, lyr["wkv"])
    k_new, v_new = kv[:, :, 0], kv[:, :, 1]
    if cfg.rope:
        p1 = pos[None]  # (1,) absolute position of this token
        q = _rope(q, p1, cfg.rope_theta)
        k_new = _rope(k_new, p1, cfg.rope_theta)
    ck = lax.dynamic_update_slice_in_dim(ck, k_new, pos, axis=1)
    cv = lax.dynamic_update_slice_in_dim(cv, v_new, pos, axis=1)
    groups = cfg.n_heads // cfg.kv_heads
    # (B, 1, Hkv, G, hd) x (B, T, Hkv, hd) -> (B, Hkv, G, T); mask j > pos
    qg = q.reshape(q.shape[0], 1, -1, groups, q.shape[-1])
    scores = jnp.einsum("bqhgk,bthk->bhgt", qg, ck) / np.sqrt(q.shape[-1])
    mask = jnp.arange(ck.shape[1])[None, None, None, :] > pos
    scores = jnp.where(mask, -jnp.inf, scores.astype(jnp.float32))
    attn = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    ctx = jnp.einsum("bhgt,bthk->bhgk", attn, cv)  # (B, Hkv, G, hd)
    ctx = ctx.reshape(ctx.shape[0], 1, -1, ctx.shape[-1])  # (B, 1, H, hd)
    o_partial = jnp.einsum("bthk,hkd->btd", ctx, lyr["wo"])
    x = x + _tp_allreduce(o_partial, wire)
    return _mlp_half(x, lyr, wire), ck, cv


def make_decode_step(cfg: TransformerConfig, mesh: Mesh):
    """One compiled incremental-decode step (the inference half of the
    model family): (params, cache, tokens (B, 1), pos) ->
    (logits (B, 1, V), cache). Batch over dp, heads + ffn over tp —
    the same tp partial-sum reductions as training, through the
    framework's ring schedule. sp/pp must be 1 on the decode mesh
    (decode is one position; pipeline decode would bubble every step).
    The cache threads through functionally — donate it at the call site
    for in-place updates."""
    for ax in ("sp", "pp"):
        if dict(mesh.shape).get(ax, 1) != 1:
            raise ValueError(f"decode mesh must have {ax}=1")
    wire = schedules.Wire(None)
    pspecs = param_specs(cfg)
    cache_spec = [{"k": _KV_SPEC, "v": _KV_SPEC}] * cfg.n_layers

    def body(params, cache, tokens, pos):
        x = params["embed"][tokens[:, :1]]
        p = pos[0]  # replicated scalar arrives as a (1,) shard
        new_cache = []
        for lyr, c in zip(params["layers"], cache):
            x, ck, cv = _decode_block(x, lyr, cfg, c["k"], c["v"], p, wire)
            new_cache.append({"k": ck, "v": cv})
        x = _rmsnorm(x, jnp.ones((cfg.d_model,), x.dtype))
        logits = jnp.einsum("btd,dv->btv", x, params["unembed"])
        return logits, new_cache

    step = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, cache_spec, P("dp", None), P()),
        out_specs=(P("dp", None), cache_spec),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(1,))


def make_train_step(cfg: TransformerConfig, mesh: Mesh, lr: float = 1e-3,
                    n_microbatches: int | None = None, remat: bool = False,
                    grad_sync: str = "leaf",
                    grad_stripes: int | None = None):
    """One compiled SGD step: forward + backward + grad sync + update, all
    inside a single shard_map program (host-only-dispatches). With a `pp`
    mesh axis the layers pipeline over it (GPipe microbatches) and params
    take the stacked form from stack_layer_params/pp_param_specs.
    remat=True rematerializes each block in the backward pass
    (jax.checkpoint), cutting peak activation memory from O(layers) to
    O(1) blocks at ~1/3 extra FLOPs — the standard long-context tradeoff.

    grad_sync picks the dp/sp gradient-sync shape: "leaf" (default, the
    original per-leaf allreduces), "striped" (bucketed: one flat
    backward-ordered gradient vector allreduced as `grad_stripes`
    independent stripe chains the backward can overlap — see
    _striped_grad_sync), or "striped_serial" (the same stripes
    barrier-serialized after the full backward, the bitwise-identical
    dispatch->compute twin). grad_stripes=None derives the stripe
    count from the cost model's argmin under the shipped calibration
    (timing.best_overlap_stripes with the shaped link and the measured
    compute term — no calibration falls back to 1, never a made-up
    depth)."""
    if grad_sync not in ("leaf", "striped", "striped_serial"):
        raise ValueError(f"unknown grad_sync {grad_sync!r}")
    wire = schedules.Wire(None)
    pp = _pp_world(mesh)
    M = (n_microbatches or pp) if pp > 1 else 1
    pspecs = pp_param_specs(cfg) if pp > 1 else param_specs(cfg)
    if grad_sync != "leaf" and pp > 1:
        raise NotImplementedError(
            "striped grad sync covers the pp=1 layer-list form")
    if grad_sync != "leaf" and grad_stripes is None:
        from ..sequencer.timing import best_overlap_stripes
        from ..telemetry import feedback as _fb

        tl = _fb.default_tier_links()
        link = tl.outer if tl is not None else _fb.default_link()
        fit = _fb.default_compute_fit()
        grad_stripes = 1
        if link is not None and fit is not None:
            nbytes = train_param_count(cfg) * 4
            sync_world = max(dict(mesh.shape).get("dp", 1),
                             dict(mesh.shape).get("sp", 1))
            grad_stripes = best_overlap_stripes(
                link, nbytes // 4, 4, max(sync_world, 2),
                compute_s=fit.seconds(nbytes), rx_buf_bytes=1024)

    def loss_fn(params, tokens, targets):
        if pp > 1:
            logits = _forward_local_pp(params, tokens, cfg, wire, M,
                                       remat=remat)
        else:
            logits = _forward_local(params, tokens, cfg, wire, remat=remat)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return nll.mean()

    def body(params, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)

        tp_world = lax.axis_size("tp")

        def sync(g, spec):
            # every param (tp-sharded or replicated) saw only its dp batch
            # shard and sp sequence shard: mean-reduce over both axes.
            g = _grad_allreduce(g, "dp", wire)
            g = _grad_allreduce(g, "sp", wire)
            if tp_world > 1:
                # The ring-allreduce transpose is itself an allreduce, so a
                # replicated cotangent entering a tp branch comes back
                # amplified by tp: tp-sharded weight grads are tp x the true
                # value (rescale), while tp-replicated params see only their
                # rank's head/ff-slice contribution (mean-allreduce over tp
                # restores the full gradient — sum of slices / tp x tp).
                if _spec_has_axis(spec, "tp"):
                    g = g / tp_world
                else:
                    g = _grad_allreduce(g, "tp", wire)
            return g

        if grad_sync == "leaf":
            grads = jax.tree.map(sync, grads, pspecs)
        else:
            grads = _striped_grad_sync(
                grads, pspecs, wire, stripes=int(grad_stripes or 1),
                serial=(grad_sync == "striped_serial"))
        if pp > 1:
            # the pipeline injects microbatches only on pp rank 0, so the
            # embed cotangent lands entirely on rank 0 (zeros elsewhere):
            # SUM-allreduce over pp replicates the full gradient. unembed
            # applies after the replicated pipeline output, so its grad is
            # already identical on every pp rank; stage (pp-sharded)
            # leaves are stage-local by construction.
            e = grads["embed"]
            esum = schedules.allreduce_ring_schedule(
                e.reshape(-1), func=ReduceFunction.SUM, axis="pp",
                world=lax.axis_size("pp"), wire=wire, seg_count=e.size,
            )
            grads = {**grads, "embed": esum.reshape(e.shape)}
        new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                  params, grads)
        for ax in ("dp", "sp"):
            loss = schedules.allreduce_ring_schedule(
                loss[None], func=ReduceFunction.SUM, axis=ax,
                world=lax.axis_size(ax), wire=wire, seg_count=1,
            )[0] / lax.axis_size(ax)
        return new_params, loss

    step = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(pspecs, P()),
        check_vma=False,
    )
    return jax.jit(step)


def shard_params(params, cfg, mesh):
    """Place a global parameter pytree according to param_specs; on a mesh
    with a pp axis the layer list is first stacked (stack_layer_params)
    and the layer dim sharded over pp."""
    if _pp_world(mesh) > 1:
        if cfg.n_layers % _pp_world(mesh):
            raise ValueError(
                f"n_layers {cfg.n_layers} must divide over pp "
                f"{_pp_world(mesh)}")
        params = stack_layer_params(params)
        specs = pp_param_specs(cfg)
    else:
        specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Device-resident train step: forward + backward + stripe-overlapped
# gradient allreduce + SGD update as ONE recorded descriptor batch
# (ROADMAP item 4's training-scale form of the stream-consumer seam)
# ---------------------------------------------------------------------------

# kernel-stream id the train step's fwd+bwd consumer registers under
# (one well-known default keeps fuzz and tests on the endpoint)
TRAIN_GRAD_STREAM = 21


def _train_leaf_shapes(cfg: TransformerConfig) -> list:
    """Leaf shapes of the flat train-step parameter vector, in the
    backward-materialization order _backward_ordered_leaves uses
    (unembed, layers N-1..0 each per _LAYER_BWD_ORDER, embed)."""
    d, ff = cfg.d_model, cfg.d_ff
    layer = {
        "w_down": (ff, d), "w_up": (d, ff), "ln2": (d,),
        "wo": (cfg.n_heads, cfg.head_dim, d),
        "wkv": (d, 2, cfg.kv_heads, cfg.head_dim),
        "wq": (d, cfg.n_heads, cfg.head_dim), "ln1": (d,),
    }
    shapes: list = [(d, cfg.vocab)]  # unembed
    for _ in range(cfg.n_layers):
        shapes.extend(layer[k] for k in _LAYER_BWD_ORDER)
    shapes.append((cfg.vocab, d))  # embed
    return shapes


def train_param_count(cfg: TransformerConfig) -> int:
    """Element count of the flat train-step parameter vector — the
    `count` of every descriptor in the fused train-step batch (and the
    gradient bytes the overlap register compares, x4)."""
    return sum(int(np.prod(s)) for s in _train_leaf_shapes(cfg))


def flatten_train_params(params: dict):
    """Parameter/gradient pytree -> flat vector in backward order (the
    layout every train-step buffer uses; see _backward_ordered_leaves
    for why the order matters to the overlap)."""
    return jnp.concatenate(
        [g.reshape(-1) for g in _backward_ordered_leaves(params)])


def unflatten_train_params(flat, cfg: TransformerConfig) -> dict:
    """Inverse of flatten_train_params (traced-value friendly)."""
    shapes = _train_leaf_shapes(cfg)
    parts = []
    off = 0
    for sh in shapes:
        size = int(np.prod(sh))
        parts.append(flat[off:off + size].reshape(sh))
        off += size
    rev_layers = []
    idx = 1
    for _ in range(cfg.n_layers):
        lyr = {}
        for k in _LAYER_BWD_ORDER:
            lyr[k] = parts[idx]
            idx += 1
        rev_layers.append(lyr)
    return {"unembed": parts[0], "embed": parts[-1],
            "layers": list(reversed(rev_layers))}


def local_train_loss(params: dict, tokens, targets,
                     cfg: TransformerConfig):
    """Mean next-token NLL of the axis-free transformer forward — the
    SAME blocks as the sharded model (_block with tp_axis=sp_axis=None:
    local causal attention, identity partial sums), so the facade train
    step runs the real model, not a stand-in."""
    x = params["embed"][tokens]
    for lyr in params["layers"]:
        x = _block(x, lyr, cfg, schedules.Wire(None),
                   tp_axis=None, sp_axis=None)
    x = _rmsnorm(x, jnp.ones((cfg.d_model,), x.dtype))
    logits = jnp.einsum("btd,dv->btv", x, params["unembed"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return nll.mean()


def make_grad_consumer(cfg: TransformerConfig, tokens, targets,
                       axis_name: str = "ccl", scale: float = 1.0):
    """The forward+backward as a RES_STREAM consumer: the copy step's
    result (this rank's flat parameter vector) runs the full local
    fwd+bwd over the rank's (batch-shard) tokens — selected by
    axis_index, so ONE traced callable serves every rank — and lands
    the flat gradient (backward order) in the result buffer. The
    parameters are the stream's data; the tokens/targets (the step's
    batch, small) close over the endpoint as program constants, so a
    new batch is a new endpoint and compiles again.

    `scale` folds into the differentiated loss (the backward's seed
    cotangent), so the consumer emits scale * grad directly. The train
    step passes -lr/world here: the dp mean and the SGD learning rate
    ride the backward, the allreduce SUMs per-rank update
    contributions, and the final combine is a pure add of two
    materialized values — no multiply ever feeds that add, so XLA
    cannot FMA-contract it differently in the fused program than in
    the eager twin (which is what keeps fused bitwise-identical to
    eager; a post-allreduce scale consumer provably broke it by an
    ULP)."""
    tok = jnp.asarray(tokens)
    tgt = jnp.asarray(targets)
    s = np.float32(scale)

    def consumer(params_flat):
        params = unflatten_train_params(
            params_flat.astype(jnp.float32), cfg)
        me = lax.axis_index(axis_name)
        t = lax.dynamic_index_in_dim(tok, me, axis=0, keepdims=False)
        g = lax.dynamic_index_in_dim(tgt, me, axis=0, keepdims=False)
        grads = jax.grad(
            lambda p: s * local_train_loss(p, t, g, cfg))(params)
        return flatten_train_params(grads).astype(params_flat.dtype)

    return consumer


def create_train_step_buffers(accl, cfg: TransformerConfig):
    """(params, grads, update, new_params) flat rank buffers for the
    fused train step, each (world, train_param_count) fp32."""
    n = train_param_count(cfg)
    return tuple(accl.create_buffer(n, np.float32) for _ in range(4))


def _register_train_consumers(accl, cfg: TransformerConfig, tokens,
                              targets, lr: float):
    # dp mean + SGD learning rate fold into the backward's seed
    # cotangent (see make_grad_consumer's scale note): each rank emits
    # its UPDATE contribution u_r = grad(-lr/world * loss_r), the
    # allreduce sums them, and the combine is a pure add
    accl.register_stream_consumer(
        TRAIN_GRAD_STREAM,
        make_grad_consumer(cfg, tokens, targets, accl.axis_name,
                           scale=-lr / accl.world))


def record_train_step(accl, cfg: TransformerConfig, tokens, targets, *,
                      lr: float = 1e-3, lint: str = "error",
                      buffers=None):
    """Record the data-parallel transformer train step as ONE
    descriptor batch over `accl`'s axis:

      1. copy(params -> grads) with the fwd+bwd spliced as its
         RES_STREAM consumer (the model compute IS in the program; the
         -lr/world update scale rides the backward seed);
      2. allreduce(grads -> update, SUM) — inside the
         OVERLAP_MIN_COUNT window this step's plan stripes into
         independent chains, and because the flat gradient is a
         backward-ordered concat whose slices simplify to the
         individual leaves, stripe i's ring chains depend only on
         stripe i's gradients: the wire runs while the rest of the
         backward materializes, in ONE jit(shard_map) program;
      3. combine(SUM, params, update -> new_params): the SGD step.

    Returns (recorder, buffers); `recorder.compile()` freezes it into
    the steady-state SequenceProgram (`make_train_step_program`), and
    the same three descriptors issued eagerly are the serial
    dispatch->compute twin (`run_train_step_eager`) — bitwise-identical
    at fp32."""
    if buffers is None:
        buffers = create_train_step_buffers(accl, cfg)
    pbuf, gbuf, ubuf, obuf = buffers
    n = train_param_count(cfg)
    _register_train_consumers(accl, cfg, tokens, targets, lr)
    seq = accl.sequence(lint=lint)
    seq.copy(pbuf, gbuf, n, res_stream=TRAIN_GRAD_STREAM)
    seq.allreduce(gbuf, ubuf, n, ReduceFunction.SUM)
    seq.combine(n, ReduceFunction.SUM, pbuf, ubuf, obuf)
    return seq, buffers


def make_train_step_program(accl, cfg: TransformerConfig, tokens,
                            targets, *, lr: float = 1e-3,
                            lint: str = "error", buffers=None):
    """The steady-state fused train step: record once, compile once,
    dispatch ONE program per iteration (the SequenceProgram seam the
    MoE layer step rides). Returns (program, buffers); the caller's
    loop is `write pbuf -> program.run() -> read obuf`."""
    seq, buffers = record_train_step(accl, cfg, tokens, targets, lr=lr,
                                    lint=lint, buffers=buffers)
    return seq.compile(), buffers


def run_train_step_eager(accl, cfg: TransformerConfig, buffers):
    """The serial dispatch->compute twin: the SAME three descriptors
    the fused batch records, issued eagerly — the compute program
    completes before the allreduce program dispatches, and the stripe
    chains (same register-selected plan) run serialized when the
    compiler's overlap_serialize twin flag is set. Three dispatches,
    intermediates kept on-device (the baseline pays the dispatch
    seams, not artificial host round trips). Bitwise-identical to the
    fused overlapped program at fp32 (fuzz-pinned)."""
    pbuf, gbuf, ubuf, obuf = buffers
    n = train_param_count(cfg)
    accl.copy_to_stream(pbuf, n, res_stream=TRAIN_GRAD_STREAM,
                        dstbuf=gbuf, from_device=True, to_device=True)
    accl.allreduce(gbuf, ubuf, n, ReduceFunction.SUM, from_device=True,
                   to_device=True)
    accl.combine(n, ReduceFunction.SUM, pbuf, ubuf, obuf,
                 from_device=True, to_device=True)
    return accl._last_request


# ---------------------------------------------------------------------------
# Device-resident decode step: N layers of KV-cached single-token
# attention + MLP, each closed by a TP partial-sum allreduce, fused
# into ONE recorded descriptor batch (the record-once/dispatch-many
# seam serving interactive traffic — ROADMAP item 4's inference half)
# ---------------------------------------------------------------------------

# kernel-stream id base for the decode step's consumers: attention for
# layer l registers at base + 2l, its MLP at base + 2l + 1, and the
# final logits head at base + 2*n_layers (distinct from
# MOE_EXPERT_STREAM=11 and TRAIN_GRAD_STREAM=21)
DECODE_STREAM_BASE = 40


def decode_attn_stream(layer: int) -> int:
    return DECODE_STREAM_BASE + 2 * layer


def decode_mlp_stream(layer: int) -> int:
    return DECODE_STREAM_BASE + 2 * layer + 1


def decode_logits_stream(cfg: TransformerConfig) -> int:
    return DECODE_STREAM_BASE + 2 * cfg.n_layers


@dataclasses.dataclass(frozen=True)
class DecodeDims:
    """Flat-buffer geometry of the fused decode step. The facade world
    is the TENSOR-PARALLEL world: each rank's state buffer carries its
    kv-head slice of the cache, and the two allreduces per layer are
    the tp partial-sum reductions of the sharded model."""

    batch: int
    max_len: int
    d_model: int
    vocab: int
    heads_local: int
    kv_heads_local: int
    ff_local: int
    # [x (B*D) | pos (B) | k-cache | v-cache], per rank
    n_state: int
    # [x (B*D) | pos (B)] on the way in, logits (B*V) on the way out —
    # one width serves both, so the x/pos prefix survives in the tail
    n_out: int


def decode_dims(cfg: TransformerConfig, world: int, batch: int,
                max_len: int) -> DecodeDims:
    for name, dim in (("n_heads", cfg.n_heads),
                      ("kv_heads", cfg.kv_heads), ("d_ff", cfg.d_ff)):
        if dim % world:
            raise ValueError(
                f"decode facade world {world} must divide {name}={dim}")
    if jnp.dtype(cfg.dtype) != jnp.float32:
        raise ValueError("the fused decode step rides fp32 rank buffers")
    kvl = cfg.kv_heads // world
    b_d = batch * cfg.d_model
    return DecodeDims(
        batch=batch, max_len=max_len, d_model=cfg.d_model,
        vocab=cfg.vocab,
        heads_local=cfg.n_heads // world, kv_heads_local=kvl,
        ff_local=cfg.d_ff // world,
        n_state=b_d + batch + 2 * batch * max_len * kvl * cfg.head_dim,
        n_out=max(batch * cfg.vocab, b_d + batch),
    )


def _rope_slots(x, pos, theta: float):
    """Per-slot rotary: (B, 1, H, D) rotated by per-slot absolute
    positions `pos` (B,) — the batched-decode form of _rope (same fp32
    half-split math), one position per batch row instead of one shared
    (T,) vector, so concurrent requests at different depths share one
    compiled step."""
    D = x.shape[-1]
    assert D % 2 == 0, "rope needs an even head_dim"
    half = D // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (B, half)
    cos = jnp.cos(ang)[:, None, None, :]
    sin = jnp.sin(ang)[:, None, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def make_decode_attn_consumer(cfg: TransformerConfig, dims: DecodeDims):
    """Layer attention as a RES_STREAM consumer over the rank's flat
    state [x, pos, kv-cache], with the rank's weight rows as operands
    (wq, wkv, wo: its head slice; ln1): rmsnorm + q/kv projection,
    per-slot RoPE, per-slot cache append at pos, masked full-length
    grouped attention, and the wo partial product — landing
    [o_partial, pos, new kv-cache] in the result buffer. One callable
    per geometry: weights of the same shapes reuse its compiled
    program."""
    B, T, D = dims.batch, dims.max_len, dims.d_model
    hd = cfg.head_dim
    hl, kvl = dims.heads_local, dims.kv_heads_local
    groups = cfg.n_heads // cfg.kv_heads

    def consumer(state, wq, wkv, wo, ln1):
        x = state[:B * D].reshape(B, 1, D)
        pos = state[B * D:B * D + B].astype(jnp.int32)
        kv = state[B * D + B:].reshape(2, B, T, kvl, hd)
        ck, cv = kv[0], kv[1]
        h = _rmsnorm(x, ln1)
        q = jnp.einsum("btd,dhk->bthk", h, wq.reshape(D, hl, hd))
        kvp = jnp.einsum("btd,dchk->btchk", h, wkv.reshape(D, 2, kvl, hd))
        k_new, v_new = kvp[:, :, 0], kvp[:, :, 1]
        if cfg.rope:
            q = _rope_slots(q, pos, cfg.rope_theta)
            k_new = _rope_slots(k_new, pos, cfg.rope_theta)
        upd = lambda c, n, p: lax.dynamic_update_slice_in_dim(  # noqa: E731
            c, n, p, axis=0)
        ck = jax.vmap(upd)(ck, k_new, pos)
        cv = jax.vmap(upd)(cv, v_new, pos)
        qg = q.reshape(B, 1, kvl, groups, hd)
        scores = jnp.einsum("bqhgk,bthk->bhgt", qg, ck) / np.sqrt(hd)
        mask = (jnp.arange(T)[None, None, None, :]
                > pos[:, None, None, None])
        scores = jnp.where(mask, -jnp.inf, scores.astype(jnp.float32))
        attn = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
        ctx = jnp.einsum("bhgt,bthk->bhgk", attn, cv)
        o_partial = jnp.einsum("bthk,hkd->btd",
                               ctx.reshape(B, 1, hl, hd),
                               wo.reshape(hl, hd, D))
        return jnp.concatenate([
            o_partial.reshape(-1).astype(state.dtype),
            pos.astype(state.dtype),
            jnp.stack([ck, cv]).reshape(-1).astype(state.dtype),
        ])

    return consumer


@functools.lru_cache(maxsize=None)
def make_decode_mlp_consumer(cfg: TransformerConfig, dims: DecodeDims):
    """Layer MLP as a RES_STREAM consumer over the flat post-attention
    residual x2 (B*D), with the rank's ff slice of w_up / w_down and
    ln2 as operands: the same math as _mlp_half's local half, emitting
    the down-projection partial sum the next allreduce closes."""
    B, D = dims.batch, dims.d_model
    ffl = dims.ff_local

    def consumer(x2_flat, w_up, w_down, ln2):
        x = x2_flat.reshape(B, 1, D)
        h = _rmsnorm(x, ln2)
        up = jax.nn.gelu(jnp.einsum("btd,df->btf", h, w_up.reshape(D, ffl)))
        down_partial = jnp.einsum("btf,fd->btd", up, w_down.reshape(ffl, D))
        return down_partial.reshape(-1).astype(x2_flat.dtype)

    return consumer


@functools.lru_cache(maxsize=None)
def make_decode_logits_consumer(cfg: TransformerConfig, dims: DecodeDims):
    """Final rmsnorm + unembed projection (the replicated head, an
    operand: every rank computes identical logits, the host reads row
    0) over the last layer's residual prefix, zero-padded to the n_out
    row width."""
    B, D, V = dims.batch, dims.d_model, dims.vocab
    n_out = dims.n_out

    def consumer(xp, unembed):
        x = xp[:B * D].reshape(B, 1, D)
        x = _rmsnorm(x, jnp.ones((D,), x.dtype))
        logits = jnp.einsum("btd,dv->btv", x, unembed.reshape(D, V))
        flat = logits.reshape(-1).astype(xp.dtype)
        pad = n_out - B * V
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    return consumer


@dataclasses.dataclass
class DecodeBuffers:
    """The fused decode step's rank buffers (each (world, n) fp32).
    `state[l]` persists layer l's kv cache across dispatches in its
    tail — only its [x, pos] prefix is re-staged per step — so the
    cache never crosses the host boundary in the steady state."""

    dims: DecodeDims
    xp: object  # [x, pos] in / logits landing width (n_out)
    logits: object  # final logits (n_out)
    state: list  # per-layer [x, pos, kv] (n_state)
    attn_sum: object  # allreduced attention output (B*D)
    x2: object  # post-attention residual (B*D)
    mlp_partial: object  # MLP consumer output (B*D)
    mlp_sum: object  # allreduced MLP output (B*D)

    @property
    def persistent(self) -> tuple:
        """The buffers whose tails are device-resident dispatch-to-
        dispatch state: the per-layer [x, pos, kv] states (the kv cache
        rides behind the refreshed [x, pos] prefix) and xp (pos rides
        behind each layer's B*D-wide residual write). Declared on the
        recorded sequence so the hazard pass can hold every OTHER
        buffer to the full ACCL101 contract."""
        return (self.xp, *self.state)


def create_decode_buffers(accl, cfg: TransformerConfig, batch: int,
                          max_len: int) -> DecodeBuffers:
    dims = decode_dims(cfg, accl.world, batch, max_len)
    b_d = batch * cfg.d_model
    return DecodeBuffers(
        dims=dims,
        xp=accl.create_buffer(dims.n_out, np.float32),
        logits=accl.create_buffer(dims.n_out, np.float32),
        state=[accl.create_buffer(dims.n_state, np.float32)
               for _ in range(cfg.n_layers)],
        attn_sum=accl.create_buffer(b_d, np.float32),
        x2=accl.create_buffer(b_d, np.float32),
        mlp_partial=accl.create_buffer(b_d, np.float32),
        mlp_sum=accl.create_buffer(b_d, np.float32),
    )


def rank_rows(accl, w, axis: int | None):
    """A weight as a (world, n) operand buffer: row r is rank r's slice
    along `axis` (None: the whole weight on every rank), flattened."""
    w = np.asarray(w, np.float32)
    world = accl.world
    if axis is None:
        rows = np.broadcast_to(w.reshape(1, -1), (world, w.size))
    else:
        rows = np.stack([part.reshape(-1)
                         for part in np.split(w, world, axis=axis)])
    return accl.create_buffer(rows.shape[1], np.float32, data=rows)


def register_decode_consumers(accl, cfg: TransformerConfig, params: dict,
                              dims: DecodeDims):
    """Register the step's consumers, each with its weights bound as
    operand buffers (the rank's head or ff slice in its row)."""
    attn = make_decode_attn_consumer(cfg, dims)
    mlp = make_decode_mlp_consumer(cfg, dims)
    for l, lyr in enumerate(params["layers"]):
        accl.register_stream_consumer(
            decode_attn_stream(l), attn,
            operands=(rank_rows(accl, lyr["wq"], 1),
                      rank_rows(accl, lyr["wkv"], 2),
                      rank_rows(accl, lyr["wo"], 0),
                      rank_rows(accl, lyr["ln1"], None)))
        accl.register_stream_consumer(
            decode_mlp_stream(l), mlp,
            operands=(rank_rows(accl, lyr["w_up"], 1),
                      rank_rows(accl, lyr["w_down"], 0),
                      rank_rows(accl, lyr["ln2"], None)))
    accl.register_stream_consumer(
        decode_logits_stream(cfg), make_decode_logits_consumer(cfg, dims),
        operands=(rank_rows(accl, params["unembed"], None),))


def _decode_layer_steps(seq_or_accl, cfg, buffers: DecodeBuffers,
                        layer: int, *, eager: bool):
    """The 7 descriptors of one decode layer — ONE list shared by the
    recorded and eager forms so the two cannot diverge:

      1. copy(xp -> state[l], B*D+B): stage [x, pos] into the state
         prefix (the kv tail survives — partial-width prefix write);
      2. copy(state[l] -> state[l], n_state) through the ATTN consumer:
         [x, pos, kv] -> [o_partial, pos, new kv] IN PLACE — the
         appended cache persists where it lives, no shuttle buffer
         (and no WAR hazard for a reordering executor to trip on);
      3. allreduce(state[l] -> attn_sum, B*D, SUM): the tp partial-sum
         reduction over the o projections (reads the state prefix);
      4. combine(SUM, xp, attn_sum -> x2, B*D): the residual add;
      5. copy(x2 -> mlp_partial, B*D) through the MLP consumer;
      6. allreduce(mlp_partial -> mlp_sum, B*D, SUM);
      7. combine(SUM, x2, mlp_sum -> xp, B*D): layer output back into
         xp's PREFIX — pos rides untouched in the tail for layer l+1.
    """
    d = buffers.dims
    b_d = d.batch * d.d_model
    n_state = buffers.state[layer].shape[-1]  # the layer's own geometry
    kw = (dict(from_device=True, to_device=True) if eager else {})
    s = seq_or_accl
    if eager:
        s.copy(buffers.xp, buffers.state[layer], b_d + d.batch,
               from_device=(layer > 0), to_device=True)
        s.copy_to_stream(buffers.state[layer], n_state,
                         res_stream=decode_attn_stream(layer),
                         dstbuf=buffers.state[layer], **kw)
    else:
        s.copy(buffers.xp, buffers.state[layer], b_d + d.batch)
        s.copy(buffers.state[layer], buffers.state[layer], n_state,
               res_stream=decode_attn_stream(layer))
    s.allreduce(buffers.state[layer], buffers.attn_sum, b_d,
                ReduceFunction.SUM, **kw)
    s.combine(b_d, ReduceFunction.SUM, buffers.xp, buffers.attn_sum,
              buffers.x2, **kw)
    if eager:
        s.copy_to_stream(buffers.x2, b_d,
                         res_stream=decode_mlp_stream(layer),
                         dstbuf=buffers.mlp_partial, **kw)
    else:
        s.copy(buffers.x2, buffers.mlp_partial, b_d,
               res_stream=decode_mlp_stream(layer))
    s.allreduce(buffers.mlp_partial, buffers.mlp_sum, b_d,
                ReduceFunction.SUM, **kw)
    s.combine(b_d, ReduceFunction.SUM, buffers.x2, buffers.mlp_sum,
              buffers.xp, **kw)


def record_decode_step(accl, cfg: TransformerConfig, params: dict, *,
                       batch: int, max_len: int, lint: str = "error",
                       buffers: DecodeBuffers | None = None):
    """Record the KV-cached single-token decode step as ONE descriptor
    batch over `accl`'s (tensor-parallel) axis: n_layers x (attention
    consumer + tp allreduce + MLP consumer + tp allreduce) + the logits
    head, 7*n_layers + 1 descriptors in one dispatch. Returns
    (recorder, buffers); `recorder.compile()` freezes the steady-state
    SequenceProgram, and the same descriptors issued eagerly
    (`run_decode_step_eager`) are the dispatch-per-layer twin —
    bitwise-identical at fp32 (the sequence-vs-eager contract,
    fuzz-pinned)."""
    if buffers is None:
        buffers = create_decode_buffers(accl, cfg, batch, max_len)
    d = buffers.dims
    register_decode_consumers(accl, cfg, params, d)
    seq = accl.sequence(lint=lint, persistent=buffers.persistent)
    for layer in range(cfg.n_layers):
        _decode_layer_steps(seq, cfg, buffers, layer, eager=False)
    seq.copy(buffers.xp, buffers.logits, d.n_out,
             res_stream=decode_logits_stream(cfg))
    return seq, buffers


def make_decode_step_program(accl, cfg: TransformerConfig, params: dict,
                             *, batch: int, max_len: int,
                             lint: str = "error",
                             buffers: DecodeBuffers | None = None):
    """The steady-state fused decode step: record once, compile once,
    dispatch ONE program per token (the SequenceProgram seam the train
    step rides, serving-side). The caller's loop is `write_decode_inputs
    -> program.run() -> read_decode_logits`."""
    seq, buffers = record_decode_step(accl, cfg, params, batch=batch,
                                      max_len=max_len, lint=lint,
                                      buffers=buffers)
    return seq.compile(), buffers


def run_decode_step_eager(accl, cfg: TransformerConfig,
                          buffers: DecodeBuffers):
    """The dispatch-per-layer twin: the SAME 7*n_layers + 1 descriptors
    the fused batch records, issued eagerly — every layer pays its
    dispatch seams while intermediates stay on-device (the same honest
    baseline shape as run_train_step_eager). Bitwise-identical to the
    fused program at fp32 (fuzz-pinned)."""
    for layer in range(len(buffers.state)):
        _decode_layer_steps(accl, cfg, buffers, layer, eager=True)
    d = buffers.dims
    accl.copy_to_stream(buffers.xp, d.n_out,
                        res_stream=decode_logits_stream(cfg),
                        dstbuf=buffers.logits, from_device=True)
    return accl._last_request


def write_decode_inputs(buffers: DecodeBuffers, params: dict, tokens,
                        pos) -> int:
    """Stage one step's inputs: embed `tokens` (B,) at per-slot
    positions `pos` (B,) into the [x, pos] prefix of every rank row of
    the xp buffer — the decode loop's host half (identical rows: the
    embedding is replicated, exactly like the sharded model's). The
    prefix lands in the host mirror and in xp's device image, and the
    step reads nothing of xp past it, so `program.run(from_device=True)`
    needs no other staging. Returns the bytes put on the device."""
    d = buffers.dims
    b_d = d.batch * d.d_model
    row = np.empty(b_d + d.batch, np.float32)
    row[:b_d] = np.asarray(params["embed"])[
        np.asarray(tokens, np.int64)].reshape(-1)
    row[b_d:] = np.asarray(pos, np.float32)
    xp = buffers.xp
    return xp.put_prefix(np.broadcast_to(row, (xp.shape[0], row.size)))


def read_decode_logits(buffers: DecodeBuffers, *,
                       sync: bool = False) -> np.ndarray:
    """The step's logits (B, V) from rank row 0 (replicated head).
    Pass sync=True after `program.run(to_device=True)` — the
    steady-state dispatch form that keeps the kv caches device-resident
    — to read them from rank 0's device row alone (the eager twin's
    final copy_to_stream already lands host-side)."""
    d = buffers.dims
    n = d.batch * d.vocab
    row = (buffers.logits.fetch_row(0, n) if sync
           else buffers.logits.host[0][:n])
    return np.asarray(row, np.float32).reshape(d.batch, d.vocab)


class FlagshipDecode:
    """The flagship transformer as `serve.DecodeServer`'s decode model:
    its buffers, fused step, eager twin, inputs and logits. It has no
    prefill program: a context streams in one token a step."""

    prefill = None

    def __init__(self, cfg: TransformerConfig, params: dict):
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.params = {
            "embed": np.asarray(params["embed"]),
            "unembed": np.asarray(params["unembed"]),
            "layers": [{k: np.asarray(v) for k, v in lyr.items()}
                       for lyr in params["layers"]],
        }

    def create_buffers(self, accl, batch: int, max_len: int):
        return create_decode_buffers(accl, self.cfg, batch, max_len)

    def make_program(self, accl, buffers, lint: str = "error"):
        d = buffers.dims
        return make_decode_step_program(
            accl, self.cfg, self.params, batch=d.batch, max_len=d.max_len,
            lint=lint, buffers=buffers)[0]

    def register_consumers(self, accl, buffers) -> None:
        register_decode_consumers(accl, self.cfg, self.params, buffers.dims)

    def run_eager(self, accl, buffers):
        return run_decode_step_eager(accl, self.cfg, buffers)

    def write_inputs(self, buffers, tokens, pos) -> int:
        return write_decode_inputs(buffers, self.params, tokens, pos)

    def read_logits(self, buffers, sync: bool = False) -> np.ndarray:
        return read_decode_logits(buffers, sync=sync)


def demo_batch(cfg, mesh, batch=4, seq=64, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return jax.device_put(tokens, sh), jax.device_put(targets, sh)
