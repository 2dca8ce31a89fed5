"""DeepSeek-V2(-Lite) decode through `ACCL.sequence()`: MLA with a latent
cache, and routed experts split over the facade's ranks.

The architecture (arXiv:2405.04434; the published config.json and
modelling code). Each layer is pre-norm, `h = x + Attn(RMSNorm(x))`,
`out = h + FFN(RMSNorm(h))`.

- Attention (MLA, no q compression). `q = x W_q`, H x (128 nope + 64
  rope). `[c, k_pe] = x W_kva`, 512 + 64; `c = RMSNorm(c)`; `[k_nope, v]
  = c W_kvb`, H x (128 + 128). Yarn rope goes on q_pe and on k_pe, one
  64-dim key shared by every head, after de-interleaving pairs (2i,
  2i+1) into halves. Softmax scale 192^-0.5 m^2, m = 0.1 mscale_all_dim
  ln(factor) + 1; causal softmax; `o = concat_h(a_h v_h) W_o`.
- Decode takes the absorbed form: the cache holds `[RMSNorm(c),
  rope(k_pe)]`, 576 numbers a token; the score is `q_nope,h W_UK,h^T c_t
  + q_pe,h k_pe,t`, the context is taken in latent space and leaves
  through W_UV,h. It equals the naive form up to rounding.
- FFN: a dense SwiGLU in the first `first_k_dense_replace` layers, then
  `y = S(x) + sum_{i in top-k(softmax(x W_g))} p_i E_i(x)`, with
  `E(x) = W_down(silu(W_gate x) * W_up x)` and S the shared experts as
  one SwiGLU of width n_shared x moe_intermediate_size.

How it rides the facade's ranks (the tensor-parallel world, one
`SequenceProgram` per step): each rank holds H/world heads of W_q,
W_kvb and W_o, the width slices of the dense MLP and the shared expert,
and n_routed_experts/world routed experts; W_kva, the norms, the router
and the head are replicated, so the latent cache is replicated too.
Attention and FFN each end in a partial sum that the step's tp allreduce
closes, so the flagship's seven descriptors a layer
(`transformer._decode_layer_steps`) serve unchanged. Weights are bound
to the consumers as operand buffers (row r: rank r's share), made on the
device from a seed or from whole parameters; the embedding stays on the
host.

`prefill` writes a slot's cache rows for a whole context: one compiled
program per chunk of positions over the facade's mesh, with the same
operand buffers, its tp reductions through the framework's ring
schedule, and the routed experts as a grouped product (`ragged_dot`)
over the (token, choice) pairs sorted by expert, with no capacity drops.

Named scopes (`mla_attn`, `router`, `experts`, `shared`, `head`) mark
the consumers' parts in the compiled programs.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..sequencer import schedules
from ..telemetry import get_tracer
from . import transformer as trf


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    """The published config's keys (those that shape the model)."""

    hidden_size: int = 2048
    num_hidden_layers: int = 27
    vocab_size: int = 102400
    rms_norm_eps: float = 1e-6
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: YarnScaling = YarnScaling()
    first_k_dense_replace: int = 1
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    tie_word_embeddings: bool = False
    # the routed experts the ranks hold together, from the first held
    # one (None: all of them); each rank holds experts_held / world
    experts_held: int | None = None
    first_held_expert: int = 0

    @classmethod
    def from_json(cls, d: dict) -> "DeepSeekV2Config":
        """From a config.json-shaped dict; keys this model does not
        read are ignored, and settings it does not implement refused."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        if "rope_scaling" in kw:
            ys = {f.name for f in dataclasses.fields(YarnScaling)}
            kw["rope_scaling"] = YarnScaling(
                **{k: v for k, v in kw["rope_scaling"].items() if k in ys})
        cfg = cls(**kw)
        if (cfg.q_lora_rank is not None or cfg.scoring_func != "softmax"
                or cfg.topk_method != "greedy" or cfg.norm_topk_prob
                or cfg.tie_word_embeddings):
            raise ValueError("only DeepSeek-V2-Lite's settings are "
                             "implemented: no q compression, softmax "
                             "scores, greedy top-k, unnormalized gates, "
                             "untied head")
        return cfg

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Numbers the latent cache holds a token: c and k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_routed_experts


# the catalog's DeepSeek-V2-Lite, whole
DEEPSEEK_V2_LITE = DeepSeekV2Config()


# -- weights -----------------------------------------------------------------

# (name, shape, split axis over the ranks or None, init) per layer kind;
# init: ("normal", std) or ("norm",) = 1 + 0.1 N(0, 1)
def _attn_weights(c: DeepSeekV2Config):
    D, H = c.hidden_size, c.num_attention_heads
    return (
        ("attn_norm", (D,), None, ("norm",)),
        ("wq", (D, H, c.q_head_dim), 1, ("normal", D ** -0.5)),
        ("wkva", (D, c.cache_width), None, ("normal", D ** -0.5)),
        ("kva_norm", (c.kv_lora_rank,), None, ("norm",)),
        ("wkvb", (c.kv_lora_rank, H, c.qk_nope_head_dim + c.v_head_dim), 1,
         ("normal", c.kv_lora_rank ** -0.5)),
        ("wo", (H, c.v_head_dim, D), 0, ("normal", (H * c.v_head_dim) ** -0.5)),
    )


def _ffn_weights(c: DeepSeekV2Config, layer: int):
    """The FFN's weights; `c` is any config with DeepSeekV2Config's
    names. A router over E experts, the held ones of them, and with
    sigmoid scores the score-correction bias of their selection."""
    D, F, E, S = (c.hidden_size, c.moe_intermediate_size,
                  c.n_routed_experts, c.shared_width)
    held = c.held_experts
    if c.is_dense(layer):
        I = c.intermediate_size  # noqa: E741
        return (
            ("ffn_norm", (D,), None, ("norm",)),
            ("w_gate", (D, I), 1, ("normal", D ** -0.5)),
            ("w_up", (D, I), 1, ("normal", D ** -0.5)),
            ("w_down", (I, D), 0, ("normal", I ** -0.5)),
        )
    bias = ((("router_bias", (E,), None, ("normal", 0.1)),)
            if c.scoring_func == "sigmoid" else ())
    return (
        ("ffn_norm", (D,), None, ("norm",)),
        ("router", (D, E), None, ("normal", D ** -0.5)),
    ) + bias + (
        ("e_gate", (held, D, F), 0, ("normal", D ** -0.5)),
        ("e_up", (held, D, F), 0, ("normal", D ** -0.5)),
        ("e_down", (held, F, D), 0, ("normal", F ** -0.5)),
        ("s_gate", (D, S), 1, ("normal", D ** -0.5)),
        ("s_up", (D, S), 1, ("normal", D ** -0.5)),
        ("s_down", (S, D), 0, ("normal", S ** -0.5)),
    )


def _head_weights(c: DeepSeekV2Config):
    D, V = c.hidden_size, c.vocab_size
    return (
        ("final_norm", (D,), None, ("norm",)),
        ("head", (D, V), None, ("normal", D ** -0.5)),
    )


_TAGS = {name: i for i, name in enumerate((
    "attn_norm", "wq", "wkva", "kva_norm", "wkvb", "wo", "ffn_norm",
    "w_gate", "w_up", "w_down", "router", "e_gate", "e_up", "e_down",
    "s_gate", "s_up", "s_down", "final_norm", "head", "embed",
    # kimi_linear's (appended, so that the tags above keep their numbers)
    "router_bias", "kda_qkv", "kda_conv", "kda_fa", "kda_fb", "kda_dt_bias",
    "kda_a_log", "kda_b", "kda_ga", "kda_gb", "kda_o_norm", "kda_o"))}


def seed_key(seed: int):
    """A threefry key from a seed of up to 64 bits."""
    seed %= 1 << 64
    return jax.random.wrap_key_data(
        jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)))


def _weight(key, layer: int, name: str, shape, init):
    """One whole weight from the seed: `layer` is the layer index, or
    num_hidden_layers for the embedding, final norm and head. `init` is
    ("normal", std), ("norm",) = 1 + 0.1 N(0, 1), or ("uniform", lo,
    hi)."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer), _TAGS[name])
    if init[0] == "uniform":
        return jax.random.uniform(k, shape, jnp.float32, init[1], init[2])
    z = jax.random.normal(k, shape, jnp.float32)
    return 1.0 + 0.1 * z if init[0] == "norm" else z * init[1]


def init_params(cfg: DeepSeekV2Config, seed: int) -> dict:
    """Whole weights (numpy) from the seed, as the reference takes them:
    {"embed", "final_norm", "head", "layers": [{name: array}]}."""
    key = seed_key(seed)
    L = cfg.num_hidden_layers
    layers = [{n: np.asarray(_weight(key, l, n, s, i))
               for n, s, _, i in _attn_weights(cfg) + _ffn_weights(cfg, l)}
              for l in range(L)]
    out = {n: np.asarray(_weight(key, L, n, s, i))
           for n, s, _, i in _head_weights(cfg)}
    out["embed"] = embedding(cfg, seed)
    out["layers"] = layers
    return out


def embedding(cfg: DeepSeekV2Config, seed: int) -> np.ndarray:
    """The (vocab, hidden) embedding on the host, std 1."""
    return np.asarray(_weight(seed_key(seed), cfg.num_hidden_layers, "embed",
                              (cfg.vocab_size, cfg.hidden_size),
                              ("normal", 1.0)))


def _view_sharding(mesh, axis_name: str, shape: tuple, axis: int | None):
    """The whole weight as a matrix in which rank r's slice along `axis`
    is the r-th block of columns (or, split along the first axis, of
    rows), laid over the ranks so: (the matrix's shape, its sharding).
    Seeded numbers depend only on an element's place in the row-major
    order, so the matrix holds the weight's numbers; and it compiles far
    faster than a 3-D array."""
    view = shape
    if len(shape) > 1:
        cut = max(axis or 0, 1)
        view = (math.prod(shape[:cut]), math.prod(shape[cut:]))
    parts = [None] * len(view)
    if axis is not None:
        parts[min(axis, 1)] = axis_name
    return view, NamedSharding(mesh, P(*parts))


@functools.lru_cache(maxsize=None)
def _rows_program(sharding, axis_name: str):
    """A laid-out view -> (world, n) rows, row r rank r's slice
    flattened. Each rank flattens the piece it holds: nothing moves
    between chips."""
    return jax.jit(jax.shard_map(
        lambda w: w.reshape(1, -1), mesh=sharding.mesh,
        in_specs=(sharding.spec,), out_specs=P(axis_name), check_vma=False))


@functools.lru_cache(maxsize=None)
def _seeded_program(name: str, shape: tuple, init, sharding):
    """(key, layer) -> the seeded weight's view, laid out by `sharding`.
    The random bits are partitionable, so each rank makes only its own
    slice."""
    return jax.jit(lambda key, layer: _weight(key, layer, name, shape, init),
                   out_shardings=sharding)


def seeded_rows(accl, key, layer: int, name: str, shape: tuple, axis,
                init):
    """The seeded weight's (world, n) rank rows, made on the devices."""
    view, sh = _view_sharding(accl.mesh, accl.axis_name, shape, axis)
    return _rows_program(sh, accl.axis_name)(
        _seeded_program(name, view, init, sh)(key, layer))


# -- decode geometry and buffers ------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLADecodeDims:
    """Flat-buffer geometry of the fused decode step: every layer's state
    is [x (B*D) | pos (B) | latent cache (B*T*cache_width)], replicated
    over the ranks; xp and logits are n_out wide. Rank r holds the routed
    experts from first_expert + r * experts_local. The cache starts at
    `cache_base` and the state is `n_state` long, both rounded up to
    `_STATE_ALIGN` numbers: on a TPU a flat (n,) array and the program's
    (1, n) rank row then share one tiled layout, so the state passes
    between them without a relayout."""

    batch: int
    max_len: int
    d_model: int
    vocab: int
    heads_local: int
    experts_local: int
    ff_local: int
    shared_local: int
    cache_width: int
    cache_base: int
    n_state: int
    n_out: int
    first_expert: int = 0


# the TPU's tile of a flat fp32 array, in numbers
_STATE_ALIGN = 1024


def _align(n: int) -> int:
    return -(-n // _STATE_ALIGN) * _STATE_ALIGN


def decode_dims(cfg: DeepSeekV2Config, world: int, batch: int,
                max_len: int) -> MLADecodeDims:
    for name, dim in (("num_attention_heads", cfg.num_attention_heads),
                      ("held experts", cfg.held_experts),
                      ("intermediate_size", cfg.intermediate_size),
                      ("shared width", cfg.shared_width)):
        if dim % world:
            raise ValueError(
                f"decode facade world {world} must divide {name}={dim}")
    D = cfg.hidden_size
    base = _align(batch * D + batch)
    return MLADecodeDims(
        batch=batch, max_len=max_len, d_model=D, vocab=cfg.vocab_size,
        heads_local=cfg.num_attention_heads // world,
        experts_local=cfg.held_experts // world,
        ff_local=cfg.intermediate_size // world,
        shared_local=cfg.shared_width // world,
        cache_width=cfg.cache_width,
        cache_base=base,
        n_state=_align(base + batch * max_len * cfg.cache_width),
        n_out=max(batch * cfg.vocab_size, batch * D + batch),
        first_expert=cfg.first_held_expert,
    )


def logits_stream(cfg: DeepSeekV2Config) -> int:
    return trf.DECODE_STREAM_BASE + 2 * cfg.num_hidden_layers


# -- the layer math, per rank ---------------------------------------------

def _rmsnorm(x, g, eps: float):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * lax.rsqrt(var + eps) * g


def _yarn_tables(cfg: DeepSeekV2Config):
    """(inv_freq, cos/sin scale) of yarn rope: the linear ramp between
    the correction dims of beta_fast and beta_slow blends the original
    and the factor-scaled frequencies. Without rope_scaling, plain
    rope."""
    y = cfg.rope_scaling
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float64) / dim
    if y is None:
        return (1.0 / base ** exps).astype(np.float32), 1.0

    def corr_dim(rot):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    extra = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = (1.0 / (y.factor * base ** exps)) * (1 - extra) \
        + (1.0 / base ** exps) * extra
    return inv.astype(np.float32), _mscale(y.factor, y.mscale) \
        / _mscale(y.factor, y.mscale_all_dim)


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _rope(x, pos, cfg: DeepSeekV2Config):
    """Yarn rope on (N, ..., d) at per-row positions pos (N,), pairs
    de-interleaved into halves first."""
    inv, m = _yarn_tables(cfg)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]
    emb = jnp.concatenate([ang, ang], -1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    cos = (jnp.cos(emb) * m).reshape(shape)
    sin = (jnp.sin(emb) * m).reshape(shape)
    d = x.shape[-1]
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (d // 2, 2)), -1, -2) \
        .reshape(x.shape)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _softmax_scale(cfg: DeepSeekV2Config) -> float:
    if cfg.rope_scaling is None:
        return cfg.q_head_dim ** -0.5
    m = _mscale(cfg.rope_scaling.factor, cfg.rope_scaling.mscale_all_dim)
    return cfg.q_head_dim ** -0.5 * m * m


def _mla(x, pos, write, w, cfg, dims, prec, rope: bool = True):
    """Absorbed MLA for N query rows x (N, D) at positions pos (N,).
    `write(new_rows)` puts the N new latent rows into the cache and
    returns the cache rows the queries attend over: (N, T, cache_width),
    each query its own slot's, or (T, cache_width), one slot's for all.
    `rope=False` leaves q_pe and k_pe unrotated (NoPE). Returns the
    rank's o-projection partial sum (N, D)."""
    D, hl = dims.d_model, dims.heads_local
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    R = cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    mm = functools.partial(jnp.einsum, precision=prec)
    with jax.named_scope("mla_attn"):
        h = _rmsnorm(x, w["attn_norm"], eps)
        q = mm("nd,dhk->nhk", h, w["wq"].reshape(D, hl, dn + dr))
        ckv = mm("nd,dc->nc", h, w["wkva"].reshape(D, R + dr))
        c = _rmsnorm(ckv[:, :R], w["kva_norm"], eps)
        k_pe, q_pe = ckv[:, R:], q[..., dn:]
        if rope:
            k_pe, q_pe = _rope(k_pe, pos, cfg), _rope(q_pe, pos, cfg)
        rows = write(jnp.concatenate([c, k_pe], -1))
        t = "nt" if rows.ndim == 3 else "t"
        wkvb = w["wkvb"].reshape(R, hl, dn + dv)
        q_lat = mm("nhk,chk->nhc", q[..., :dn], wkvb[..., :dn])
        s = (mm(f"nhc,{t}c->nht", q_lat, rows[..., :R])
             + mm(f"nhr,{t}r->nht", q_pe, rows[..., R:]))
        s = s * _softmax_scale(cfg)
        T = rows.shape[-2]
        s = jnp.where(jnp.arange(T)[None, None, :] > pos[:, None, None],
                      -jnp.inf, s)
        p = jax.nn.softmax(s, -1)
        ctx = mm(f"nht,{t}c->nhc", p, rows[..., :R])
        o = mm("nhc,chv->nhv", ctx, wkvb[..., dn:])
        return mm("nhv,hvd->nd", o, w["wo"].reshape(hl, dv, D))


def _swiglu(h, w_gate, w_up, w_down, prec):
    mm = functools.partial(jnp.matmul, precision=prec)
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def _local_experts(h, top_p, top_i, w, dims, e0, prec):
    """This rank's routed experts [e0, e0 + experts_local) on the tokens
    routed to them: the (token, choice) pairs sorted by expert, a
    grouped product over the sorted rows, weighted, back in pair order
    and summed over each token's choices. Pairs of other ranks' experts
    sort last, outside every group, and are zeroed by a select: on the
    chip the grouped product leaves rows outside every group unwritten,
    not zero, so a weight of 0 would not do (0 x NaN)."""
    N, k = top_i.shape
    D, El, F = dims.d_model, dims.experts_local, w["e_gate"].size // (
        dims.experts_local * dims.d_model)
    e = top_i.reshape(-1) - e0
    local = (e >= 0) & (e < El)
    group = jnp.where(local, e, El)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=El + 1)[:El].astype(jnp.int32)
    xs = h[order // k]
    rd = functools.partial(lax.ragged_dot, group_sizes=sizes, precision=prec)
    a = rd(xs, w["e_gate"].reshape(El, D, F))
    b = rd(xs, w["e_up"].reshape(El, D, F))
    y = rd(jax.nn.silu(a) * b, w["e_down"].reshape(El, F, D))
    y = jnp.where(local[order][:, None], y * top_p.reshape(-1)[order][:, None],
                  0.0)[jnp.argsort(order)]
    return y.reshape(N, k, D).sum(1)


def _route(logits, w, cfg):
    """(gates, experts) of each token's top-k over the router logits.
    softmax: the top-k probabilities (DeepSeek-V2). sigmoid: the top-k
    of the scores plus the score-correction bias, which only selects;
    the gates are the chosen scores, renormalized over the k with
    norm_topk_prob (DeepSeek-V3's gate). Both times the routed scale."""
    k = cfg.num_experts_per_tok
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_i = lax.top_k(scores + w["router_bias"], k)
        top_p = jnp.take_along_axis(scores, top_i, -1)
        if cfg.norm_topk_prob:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    else:
        top_p, top_i = lax.top_k(jax.nn.softmax(logits, -1), k)
    return top_p * cfg.routed_scaling_factor, top_i


def _ffn(x, w, cfg, dims, layer: int, axis: str, prec):
    """The rank's partial sum of the layer's FFN on x (N, D)."""
    h = _rmsnorm(x, w["ffn_norm"], cfg.rms_norm_eps)
    D = dims.d_model
    if cfg.is_dense(layer):
        fl = dims.ff_local
        return _swiglu(h, w["w_gate"].reshape(D, fl),
                       w["w_up"].reshape(D, fl), w["w_down"].reshape(fl, D),
                       prec)
    with jax.named_scope("router"):
        logits = jnp.matmul(h, w["router"].reshape(D, cfg.n_routed_experts),
                            precision=prec)
        top_p, top_i = _route(logits, w, cfg)
    with jax.named_scope("experts"):
        e0 = dims.first_expert + lax.axis_index(axis) * dims.experts_local
        y = _local_experts(h, top_p, top_i, w, dims, e0, prec)
    with jax.named_scope("shared"):
        sl = dims.shared_local
        y = y + _swiglu(h, w["s_gate"].reshape(D, sl),
                        w["s_up"].reshape(D, sl), w["s_down"].reshape(sl, D),
                        prec)
    return y


def _names(spec) -> tuple:
    return tuple(n for n, _, _, _ in spec)


@functools.lru_cache(maxsize=None)
def make_attn_consumer(cfg: DeepSeekV2Config, dims: MLADecodeDims,
                       prec: str, rope: bool = True):
    """Layer attention over the rank's flat state [x, pos, cache] with
    the layer's attention weights as operands: the new latent row of
    each slot goes into the cache at its position, and the step lands
    [o_partial, pos, cache] back in the state. Only the o prefix and
    the B new rows are written, each in place, so the rest of the
    state is never rebuilt."""
    B, D, T, W = dims.batch, dims.d_model, dims.max_len, dims.cache_width
    base = dims.cache_base
    names = _names(_attn_weights(cfg))

    def consumer(state, *rows):
        x = state[:B * D].reshape(B, D)
        pos = state[B * D:B * D + B].astype(jnp.int32)
        put = {}

        def write(new):  # one row a slot, at its position, in place
            s = state
            for b in range(B):
                s = lax.dynamic_update_slice_in_dim(
                    s, new[b], base + b * (T * W) + pos[b] * W, 0)
            put["state"] = s
            return s[base:base + B * T * W].reshape(B, T, W)

        o = _mla(x, pos, write, dict(zip(names, rows)), cfg, dims, prec,
                 rope)
        return lax.dynamic_update_slice_in_dim(put["state"], o.reshape(-1),
                                               0, 0)

    return consumer


@functools.lru_cache(maxsize=None)
def make_ffn_consumer(cfg: DeepSeekV2Config, dims: MLADecodeDims,
                      dense: bool, axis: str, prec: str):
    """The layer's FFN over the post-attention residual (B*D): the dense
    SwiGLU's width slice, or the router over every expert with this
    rank's experts and its slice of the shared expert."""
    B, D = dims.batch, dims.d_model
    layer = 0 if dense else cfg.first_k_dense_replace
    names = _names(_ffn_weights(cfg, layer))

    def consumer(x_flat, *rows):
        y = _ffn(x_flat.reshape(B, D), dict(zip(names, rows)), cfg, dims,
                 layer, axis, prec)
        return y.reshape(-1)

    return consumer


@functools.lru_cache(maxsize=None)
def make_logits_consumer(cfg: DeepSeekV2Config, dims: MLADecodeDims,
                         prec: str):
    """Final norm and the replicated head over the last residual,
    zero-padded to the n_out row width."""
    B, D, V = dims.batch, dims.d_model, dims.vocab

    def consumer(xp, final_norm, head):
        with jax.named_scope("head"):
            x = _rmsnorm(xp[:B * D].reshape(B, D), final_norm,
                         cfg.rms_norm_eps)
            flat = jnp.matmul(x, head.reshape(D, V), precision=prec) \
                .reshape(-1)
        pad = dims.n_out - B * V
        return jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)]) \
            if pad else flat

    return consumer


# -- the model on a facade ------------------------------------------------

class DeepSeekV2:
    """DeepSeek-V2 weights on `accl`'s ranks, and the decode model
    `serve.DecodeServer` drives: buffers, the fused step, its eager
    twin, inputs, logits and prefill.

    Weights come from `params` (whole arrays, `init_params`' layout) or,
    without them, from `seed`, generated on the device straight into
    the rank rows. `precision` is the consumers' and prefill's matmul
    precision: "highest" computes in fp32; "default" is the control."""

    def __init__(self, accl, cfg: DeepSeekV2Config, *, params=None,
                 seed: int | None = None, precision: str = "highest",
                 prefill_chunk: int = 512):
        if (params is None) == (seed is None):
            raise ValueError("give whole params or a seed")
        self.accl = accl
        self.cfg = cfg
        self.vocab = cfg.vocab_size
        self.precision = precision
        self.prefill_chunk = prefill_chunk
        L = cfg.num_hidden_layers
        self.embed = (np.asarray(params["embed"], np.float32)
                      if params is not None else embedding(cfg, seed))
        key = None if seed is None else seed_key(seed)

        def buffers(spec, layer, whole):
            out = []
            for name, shape, axis, init in spec:
                out.append(
                    trf.rank_rows(accl, whole[name], axis)
                    if whole is not None else accl.create_device_buffer(
                        seeded_rows(accl, key, layer, name, shape, axis,
                                    init)))
            return tuple(out)

        self.attn_operands = []
        self.ffn_operands = []
        for l in range(L):
            lyr = None if params is None else params["layers"][l]
            self.attn_operands.append(buffers(self._attn_spec(l), l, lyr))
            self.ffn_operands.append(buffers(_ffn_weights(cfg, l), l, lyr))
        self.head_operands = buffers(_head_weights(cfg), L, params)
        self._prefill_fn = None

    @property
    def layer_counts(self) -> dict:
        """The counts of each kind of layer a step runs (on the step's
        call span)."""
        return {"mla_layers": self.cfg.num_hidden_layers}

    @property
    def operand_buffers(self) -> list:
        return [b for ops in (*self.attn_operands, *self.ffn_operands,
                              self.head_operands) for b in ops]

    def free(self) -> None:
        """Release every weight buffer."""
        for b in self.operand_buffers:
            self.accl.free_buffer(b)
            b.device = None
        self.attn_operands, self.ffn_operands, self.head_operands = [], [], ()

    # -- what a model of another layer mix overrides --------------------------

    def _attn_spec(self, layer: int):
        """The attention weights of `layer`."""
        return _attn_weights(self.cfg)

    def _states(self, dims: MLADecodeDims) -> list[tuple[int, str]]:
        """(width, role) of each layer's state buffer: the role names
        what it holds on the sequence's compile span."""
        return [(dims.n_state, "mla_cache")] * self.cfg.num_hidden_layers

    def _attn_consumer(self, layer: int, dims: MLADecodeDims):
        return make_attn_consumer(self.cfg, dims, self.precision)

    # -- the decode model ---------------------------------------------------

    def create_buffers(self, accl, batch: int, max_len: int):
        dims = decode_dims(self.cfg, accl.world, batch, max_len)
        b_d = batch * dims.d_model
        sharding = NamedSharding(accl.mesh, P(accl.axis_name, None))

        def state(width: int, role: str):
            buf = accl.create_device_buffer(jax.jit(
                lambda: jnp.zeros((accl.world, width), jnp.float32),
                out_shardings=sharding)())
            buf.role = role
            return buf

        return trf.DecodeBuffers(
            dims=dims,
            xp=accl.create_buffer(dims.n_out, np.float32),
            logits=accl.create_buffer(dims.n_out, np.float32),
            state=[state(*s) for s in self._states(dims)],
            attn_sum=accl.create_buffer(b_d, np.float32),
            x2=accl.create_buffer(b_d, np.float32),
            mlp_partial=accl.create_buffer(b_d, np.float32),
            mlp_sum=accl.create_buffer(b_d, np.float32),
        )

    def register_consumers(self, accl, buffers) -> None:
        cfg, dims, prec = self.cfg, buffers.dims, self.precision
        for l in range(cfg.num_hidden_layers):
            accl.register_stream_consumer(
                trf.decode_attn_stream(l), self._attn_consumer(l, dims),
                operands=self.attn_operands[l])
            accl.register_stream_consumer(
                trf.decode_mlp_stream(l),
                make_ffn_consumer(cfg, dims, cfg.is_dense(l),
                                  accl.axis_name, prec),
                operands=self.ffn_operands[l])
        accl.register_stream_consumer(
            logits_stream(cfg), make_logits_consumer(cfg, dims, prec),
            operands=self.head_operands)

    def record(self, accl, buffers, lint: str = "error"):
        """The step recorded as one descriptor batch: the flagship's
        seven descriptors a layer, then the head."""
        self.register_consumers(accl, buffers)
        seq = accl.sequence(lint=lint, persistent=buffers.persistent)
        for layer in range(self.cfg.num_hidden_layers):
            trf._decode_layer_steps(seq, self.cfg, buffers, layer,
                                    eager=False)
        seq.copy(buffers.xp, buffers.logits, buffers.dims.n_out,
                 res_stream=logits_stream(self.cfg))
        return seq

    def make_program(self, accl, buffers, lint: str = "error"):
        return self.record(accl, buffers, lint).compile()

    def run_eager(self, accl, buffers):
        for layer in range(self.cfg.num_hidden_layers):
            trf._decode_layer_steps(accl, self.cfg, buffers, layer,
                                    eager=True)
        accl.copy_to_stream(buffers.xp, buffers.dims.n_out,
                            res_stream=logits_stream(self.cfg),
                            dstbuf=buffers.logits, from_device=True)

    def write_inputs(self, buffers, tokens, pos) -> int:
        return trf.write_decode_inputs(buffers, {"embed": self.embed},
                                       tokens, pos)

    def read_logits(self, buffers, sync: bool = False) -> np.ndarray:
        return trf.read_decode_logits(buffers, sync=sync)

    # -- prefill ------------------------------------------------------------

    def _prefill_program(self, dims: MLADecodeDims):
        """One chunk of one slot's positions through every layer: the
        chunk's latent rows go into the slot's cache rows of each
        layer's state (donated, so updated in place). Its arguments:
        the chunk's embedded tokens, [[slot, first position, tokens in
        the chunk]], the states, the operands."""
        accl, cfg, prec = self.accl, self.cfg, self.precision
        axis = accl.axis_name
        B, D, T, W = dims.batch, dims.d_model, dims.max_len, dims.cache_width
        L = cfg.num_hidden_layers
        C = self.prefill_chunk
        attn_names = _names(_attn_weights(cfg))
        ffn_names = [_names(_ffn_weights(cfg, l)) for l in range(L)]
        n_attn, n_ffn = (len(attn_names),
                         [len(n) for n in ffn_names])
        wire = schedules.Wire(None)

        def tp_sum(y):
            return trf._tp_allreduce(y, wire, axis)

        def body(x, where, *flat):
            x = x.reshape(C, D)
            slot, p0 = where[0, 0], where[0, 1]
            states = [s.reshape(-1) for s in flat[:L]]
            ops = [o.reshape(-1) for o in flat[L:]]
            pos = p0 + jnp.arange(C)
            out = []
            at = 0
            for l in range(L):
                aw = dict(zip(attn_names, ops[at:at + n_attn]))
                at += n_attn
                fw = dict(zip(ffn_names[l], ops[at:at + n_ffn[l]]))
                at += n_ffn[l]
                state = states[l]
                base = dims.cache_base + slot * (T * W)  # the slot's rows
                rows = lax.dynamic_slice_in_dim(state, base, T * W) \
                    .reshape(T, W)
                put = {}

                def write(new, rows=rows, put=put):
                    put["new"] = new
                    return lax.dynamic_update_slice_in_dim(rows, new, p0, 0)

                x = x + tp_sum(_mla(x, pos, write, aw, cfg, dims, prec))
                x = x + tp_sum(_ffn(x, fw, cfg, dims, l, axis, prec))
                # only the chunk's rows change: written in place
                out.append(lax.dynamic_update_slice_in_dim(
                    state, put["new"].reshape(-1), base + p0 * W, 0)
                    .reshape(1, -1))
            return tuple(out)

        spec = P(axis)
        n_ops = L * n_attn + sum(n_ffn)
        fn = jax.shard_map(
            body, mesh=accl.mesh,
            in_specs=(P(), P()) + (spec,) * (L + n_ops),
            out_specs=(spec,) * L, check_vma=False)
        return jax.jit(fn, donate_argnums=tuple(range(2, 2 + L)))

    def prefill(self, buffers, slot: int, tokens) -> None:
        """Write slot `slot`'s cache rows for positions [0, len(tokens))
        of every layer, as decoding the tokens one by one would. Runs
        in chunks of `prefill_chunk` positions; positions past the
        tokens in the last chunk are written too, and masked until the
        slot's own decode steps overwrite them."""
        dims = buffers.dims
        tokens = np.asarray(tokens, np.int64)
        n = len(tokens)
        C = self.prefill_chunk
        chunks = -(-n // C)
        if chunks * C > dims.max_len:
            raise ValueError(f"a context of {n} in chunks of {C} passes "
                             f"max_len {dims.max_len}")
        if not 0 <= slot < dims.batch:
            raise ValueError(f"slot {slot} outside batch {dims.batch}")
        if self._prefill_fn is None or self._prefill_fn[0] != dims:
            self._prefill_fn = (dims, self._prefill_program(dims))
        fn = self._prefill_fn[1]
        ops = [b.device for b in (
            b for l in range(self.cfg.num_hidden_layers)
            for b in (*self.attn_operands[l], *self.ffn_operands[l]))]
        padded = np.zeros(chunks * C, np.int64)
        padded[:n] = tokens
        with get_tracer().span("prefill", cat="phase", track="facade",
                               slot=int(slot), tokens=n, chunks=chunks):
            for i in range(chunks):
                x = self.embed[padded[i * C:(i + 1) * C]]
                where = np.array([[slot, i * C, min(C, n - i * C)]],
                                 np.int32)
                states = fn(x, where, *(s.device for s in buffers.state),
                            *ops)
                for buf, new in zip(buffers.state, states):
                    buf.device = new
            jax.block_until_ready([s.device for s in buffers.state])
