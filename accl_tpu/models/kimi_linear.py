"""Kimi-Linear decode through `ACCL.sequence()`: Kimi Delta Attention
(KDA) layers with a fixed-size recurrent state beside MLA layers with a
latent cache, and sigmoid-routed experts of which this chip holds a
stated share.

The architecture (arXiv:2510.26692; the published config.json; the
`fla` library's KimiDeltaAttention). Each layer is pre-norm, `h = x +
Attn(RMSNorm(x))`, `out = h + FFN(RMSNorm(h))`; the layers named in
`full_attn_layers` (1-based) are MLA, the others KDA.

- KDA, H heads of dk = dv = K, per head and token t: `q, k, v =
  SiLU(ShortConv(h W_{q,k,v}))`, a causal depthwise convolution of
  `short_conv_kernel_size` taps over time; q and k L2-normalized, q
  times K^-0.5; `log a_t = -exp(A_log) softplus(h W_fa W_fb + dt_bias)`
  per channel; `b_t = sigmoid(h W_b)` per head; `S_t = (I - b_t k_t
  k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T`; `o_t = S_t^T q_t`; `out =
  [RMSNorm_head(o_t) * sigmoid(h W_ga W_gb)] W_o`.
- MLA as deepseek_v2's absorbed form, with no rotation (`mla_use_nope`)
  and softmax scale (dn + dr)^-0.5.
- FFN: a dense SwiGLU in the first `first_k_dense_replace` layers, then
  sigmoid scores over every routed expert, top-k of the scores plus a
  score-correction bias, gates the chosen scores renormalized over the
  k and times `routed_scaling_factor`, plus one shared expert. The chip
  holds `experts_held` of the experts from `first_held_expert`: tokens
  routed elsewhere add nothing here (the exchange of expert parallelism
  is not part of a one-chip program).

Decode state, per layer, in one flat fp32 row: MLA `[x | pos | latent
cache]` (deepseek_v2.MLADecodeDims); KDA `[x | pos | conv tails | S]`
(`KDADims`), the conv tails the last kernel - 1 projected inputs of
each slot, and S each slot's H x K x K state, every block on the flat
tile of 1024 numbers. The KDA consumer writes the o prefix, the tails
and S in place. A slot at position 0 starts from a zero state, in the
step and in the prefill: S, unlike the cache, is not masked by
position.

Prefill runs a slot's context in chunks of `prefill_chunk` positions,
one compiled program a chunk; KDA inside a chunk in the chunkwise (WY)
form over sub-chunks of `KDA_CHUNK` positions.

Attention is data-parallel in the deployment (every chip holds every
head and its own sessions), so the model runs on a facade of one rank.
The consumers' parts carry named scopes: `kda`, and deepseek_v2's
`mla_attn`, `router`, `experts`, `shared`, `head`.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import deepseek_v2 as ds

# positions of one step of the chunkwise KDA form inside a prefill chunk
KDA_CHUNK = 16


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published config's keys that shape the model, under the
    names the shared deepseek_v2 code reads (`from_json` maps Kimi's)."""

    hidden_size: int = 2304
    num_hidden_layers: int = 27
    vocab_size: int = 163840
    rms_norm_eps: float = 1e-5
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 256  # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)  # 1-based
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    experts_held: int | None = None
    first_held_expert: int = 0

    rope_scaling = None  # NoPE: nothing is rotated

    @classmethod
    def from_json(cls, d: dict) -> "KimiLinearConfig":
        """From a config.json-shaped dict. Where `router_experts` is
        given, `num_experts` counts the experts held here and
        `router_experts` the router's width."""
        if (d.get("q_lora_rank") is not None or not d.get("mla_use_nope")
                or d.get("num_expert_group", 1) != 1
                or d.get("moe_router_activation_func") != "sigmoid"
                or d.get("tie_word_embeddings")):
            raise ValueError("only Kimi-Linear's settings are implemented: "
                             "no q compression, NoPE MLA, sigmoid scores "
                             "in one group, untied head")
        lin = d["linear_attn_config"]
        kw = {f.name: d[f.name] for f in dataclasses.fields(cls)
              if f.name in d}
        held = "router_experts" in d
        kw.update(
            n_routed_experts=d["router_experts"] if held else d["num_experts"],
            experts_held=d["num_experts"] if held else None,
            num_experts_per_tok=d["num_experts_per_token"],
            n_shared_experts=d["num_shared_experts"],
            norm_topk_prob=d["moe_renormalize"],
            scoring_func=d["moe_router_activation_func"],
            full_attn_layers=tuple(lin["full_attn_layers"]),
            kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            short_conv_kernel_size=lin["short_conv_kernel_size"])
        return cls(**kw)

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def kda_width(self) -> int:
        return self.kda_num_heads * self.kda_head_dim

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def is_mla(self, layer: int) -> bool:
        """0-based `layer` is a full-attention (MLA) layer."""
        return layer + 1 in self.full_attn_layers


# -- weights -----------------------------------------------------------------

def _kda_weights(c: KimiLinearConfig):
    """(name, shape, split axis, init) of a KDA layer's attention. The
    gates' rank is the head dim, as in fla; A_log and dt_bias are drawn
    uniform over fla's ranges (A = exp(A_log) in [1, 16], softplus(
    dt_bias) in [0.001, 0.1])."""
    D, H, K = c.hidden_size, c.kda_num_heads, c.kda_head_dim
    HK, taps = c.kda_width, c.short_conv_kernel_size
    return (
        ("attn_norm", (D,), None, ("norm",)),
        ("kda_qkv", (D, 3 * HK), None, ("normal", D ** -0.5)),
        ("kda_conv", (3, taps, HK), None, ("normal", taps ** -0.5)),
        ("kda_fa", (D, K), None, ("normal", D ** -0.5)),
        ("kda_fb", (K, HK), None, ("normal", K ** -0.5)),
        ("kda_dt_bias", (HK,), None, ("uniform", math.log(math.expm1(1e-3)),
                                      math.log(math.expm1(0.1)))),
        ("kda_a_log", (H,), None, ("uniform", 0.0, math.log(16.0))),
        ("kda_b", (D, H), None, ("normal", D ** -0.5)),
        ("kda_ga", (D, K), None, ("normal", D ** -0.5)),
        ("kda_gb", (K, HK), None, ("normal", K ** -0.5)),
        ("kda_o_norm", (K,), None, ("norm",)),
        ("kda_o", (HK, D), None, ("normal", HK ** -0.5)),
    )


def attn_weights(c: KimiLinearConfig, layer: int):
    return ds._attn_weights(c) if c.is_mla(layer) else _kda_weights(c)


# -- the KDA state's geometry -----------------------------------------------

@dataclasses.dataclass(frozen=True)
class KDADims:
    """A KDA layer's flat state: [x (B*D) | pos (B) | conv tails (B x
    (taps-1) x 3 x HK) | S (B x H x K x K)], the tails from `tail_base`,
    S from `s_base`, `n_state` long, each rounded up to the flat tile."""

    batch: int
    d_model: int
    heads: int
    head_dim: int
    taps: int
    tail_base: int
    s_base: int
    n_state: int

    @property
    def tail_size(self) -> int:  # one slot's tails
        return (self.taps - 1) * 3 * self.heads * self.head_dim

    @property
    def s_size(self) -> int:  # one slot's S
        return self.heads * self.head_dim * self.head_dim


def kda_dims(cfg: KimiLinearConfig, batch: int) -> KDADims:
    H, K = cfg.kda_num_heads, cfg.kda_head_dim
    taps = cfg.short_conv_kernel_size
    tail_base = ds._align(batch * cfg.hidden_size + batch)
    s_base = ds._align(tail_base + batch * (taps - 1) * 3 * H * K)
    return KDADims(batch=batch, d_model=cfg.hidden_size, heads=H,
                   head_dim=K, taps=taps, tail_base=tail_base, s_base=s_base,
                   n_state=ds._align(s_base + batch * H * K * K))


# -- the KDA math -------------------------------------------------------------

def _short_conv(seq, w):
    """SiLU of the causal depthwise convolution over time (axis 0):
    seq (taps - 1 + T, ..., 3, HK) -> (T, ..., 3, HK); w (3, taps, HK),
    tap j on input t - taps + 1 + j."""
    taps = w.shape[1]
    T = seq.shape[0] - taps + 1
    return jax.nn.silu(sum(seq[j:j + T] * w[:, j] for j in range(taps)))


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def _decay(h, w, cfg, prec):
    """log a (N, H, K): -exp(A_log) softplus(h W_fa W_fb + dt_bias)."""
    N, H, K = h.shape[0], cfg.kda_num_heads, cfg.kda_head_dim
    mm = functools.partial(jnp.matmul, precision=prec)
    f = mm(mm(h, w["kda_fa"].reshape(-1, K)), w["kda_fb"].reshape(K, -1))
    f = jax.nn.softplus(f + w["kda_dt_bias"]).reshape(N, H, K)
    return -jnp.exp(w["kda_a_log"])[:, None] * f


def _beta(h, w, cfg, prec):
    """b (N, H) = sigmoid(h W_b)."""
    return jax.nn.sigmoid(jnp.matmul(
        h, w["kda_b"].reshape(-1, cfg.kda_num_heads), precision=prec))


def _kda_inputs(conv, h, w, cfg, prec):
    """(q, k, v, log a, b) of N tokens from their convolved projections
    conv (N, 3, HK) and normed inputs h (N, D)."""
    N, H, K = h.shape[0], cfg.kda_num_heads, cfg.kda_head_dim
    q, k, v = (conv[:, i].reshape(N, H, K) for i in range(3))
    return (_l2norm(q) * K ** -0.5, _l2norm(k), v, _decay(h, w, cfg, prec),
            _beta(h, w, cfg, prec))


def _kda_output(o, h, w, cfg, prec):
    """[RMSNorm_head(o) * sigmoid(h W_ga W_gb)] W_o for o (N, H, K)."""
    N, K = h.shape[0], cfg.kda_head_dim
    mm = functools.partial(jnp.matmul, precision=prec)
    gate = mm(mm(h, w["kda_ga"].reshape(-1, K)), w["kda_gb"].reshape(K, -1))
    o = ds._rmsnorm(o, w["kda_o_norm"], cfg.rms_norm_eps).reshape(N, -1)
    return mm(o * jax.nn.sigmoid(gate), w["kda_o"].reshape(o.shape[1], -1))


def _kda_step(S, q, k, v, g, b, prec):
    """One token of the gated delta rule for N slots: S (N, H, K, V)
    decayed per key channel, its prediction of v replaced along k by
    b (v - S^T k); returns (new S, o = S^T q)."""
    es = functools.partial(jnp.einsum, precision=prec)
    S = S * jnp.exp(g)[..., None]
    err = v - es("nhk,nhkv->nhv", k, S)
    S = S + (b[..., None] * k)[..., None] * err[..., None, :]
    return S, es("nhk,nhkv->nhv", q, S)


def _unit_lower_inverse(A, prec):
    """(I + A)^-1 for strictly lower triangular A (..., c, c), c a power
    of two: the product of (I + (-A)^(2^j)), exact because A^c = 0."""
    c = A.shape[-1]
    mm = functools.partial(jnp.matmul, precision=prec)
    n = -A
    inv = jnp.eye(c, dtype=A.dtype) + n
    for _ in range(int(math.log2(c)) - 1):
        n = mm(n, n)
        inv = inv + mm(inv, n)
    return inv


def _kda_chunk(S, q, k, v, g, b, prec):
    """c tokens of one slot in the chunkwise (WY) form: S (H, K, V); q,
    k, g (c, H, K), v (c, H, V), b (c, H). With B_t the running sum of
    g and U the pseudo-values, (I + A) U = b (v - (k e^B) S) with A_ti =
    b_t sum_k k_t k_i e^(B_t - B_i) for i < t; o_t = (q_t e^(B_t)) S +
    sum_(i<=t) q_t k_i e^(B_t - B_i) U_i; S' = e^(B_c) S + sum_i k_i
    e^(B_c - B_i) U_i. Every exponent is <= 0."""
    es = functools.partial(jnp.einsum, precision=prec)
    c = q.shape[0]
    B = jnp.cumsum(g, 0)
    incl = jnp.tril(jnp.ones((c, c), bool))
    rel = jnp.exp(jnp.where(incl[:, :, None, None],
                            B[:, None] - B[None, :], -jnp.inf))  # (t, i, H, K)
    A = es("thk,ihk,tihk->hti", b[..., None] * k, k, rel)
    A = jnp.where(incl & ~jnp.eye(c, dtype=bool), A, 0.0)
    eB = jnp.exp(B)
    rhs = b[..., None] * (v - es("thk,hkv->thv", k * eB, S))
    U = es("hti,ihv->thv", _unit_lower_inverse(A, prec), rhs)
    o = (es("thk,hkv->thv", q * eB, S)
         + es("thk,ihk,tihk,ihv->thv", q, k, rel, U))
    last = B[-1]
    S = jnp.exp(last)[..., None] * S \
        + es("ihk,ihv->hkv", k * jnp.exp(last - B), U)
    return S, o


def _kda_prefill(x, tails, S, n_valid, w, cfg, prec):
    """One slot's chunk of C positions x (C, D) from its state (tails
    (taps-1, 3, HK), S (H, K, V)); the first n_valid positions are its
    tokens, the rest leave the state as it is. Returns (out (C, D), new
    tails, new S)."""
    C, H, K = x.shape[0], cfg.kda_num_heads, cfg.kda_head_dim
    taps = cfg.short_conv_kernel_size
    h = ds._rmsnorm(x, w["attn_norm"], cfg.rms_norm_eps)
    raw = jnp.matmul(h, w["kda_qkv"].reshape(-1, 3 * H * K),
                     precision=prec).reshape(C, 3, H * K)
    seq = jnp.concatenate([tails, raw], 0)
    q, k, v, g, b = _kda_inputs(
        _short_conv(seq, w["kda_conv"].reshape(3, taps, -1)), h, w, cfg,
        prec)
    valid = jnp.arange(C) < n_valid
    g = jnp.where(valid[:, None, None], g, 0.0)
    b = jnp.where(valid[:, None], b, 0.0)
    n = C // KDA_CHUNK

    def chunk(S, xs):
        return _kda_chunk(S, *xs, prec)

    S, o = lax.scan(chunk, S, tuple(
        t.reshape((n, KDA_CHUNK) + t.shape[1:]) for t in (q, k, v, g, b)))
    out = _kda_output(o.reshape(C, H, K), h, w, cfg, prec)
    return out, lax.dynamic_slice_in_dim(seq, n_valid, taps - 1, 0), S


@functools.lru_cache(maxsize=None)
def make_kda_consumer(cfg: KimiLinearConfig, kd: KDADims, prec: str):
    """Layer KDA over the flat state [x, pos, tails, S] with the layer's
    weights as operands: one token a slot through the short conv and
    the delta rule; the o prefix, the new tails and the new S are
    written in place, and nothing else of the state changes. A slot at
    position 0 starts from zero tails and a zero S."""
    B, D = kd.batch, kd.d_model
    H, K, taps = kd.heads, kd.head_dim, kd.taps
    names = ds._names(_kda_weights(cfg))

    def consumer(state, *rows):
        with jax.named_scope("kda"):
            w = dict(zip(names, rows))
            x = state[:B * D].reshape(B, D)
            fresh = (state[B * D:B * D + B] == 0)[:, None, None, None]
            tails = state[kd.tail_base:kd.tail_base + B * kd.tail_size] \
                .reshape(B, taps - 1, 3, H * K)
            S = state[kd.s_base:kd.s_base + B * kd.s_size] \
                .reshape(B, H, K, K)
            h = ds._rmsnorm(x, w["attn_norm"], cfg.rms_norm_eps)
            raw = jnp.matmul(h, w["kda_qkv"].reshape(D, 3 * H * K),
                             precision=prec).reshape(B, 1, 3, H * K)
            window = jnp.concatenate(
                [jnp.where(fresh, 0.0, tails), raw], 1)
            conv = _short_conv(jnp.swapaxes(window, 0, 1),
                               w["kda_conv"].reshape(3, taps, H * K))[0]
            q, k, v, g, b = _kda_inputs(conv, h, w, cfg, prec)
            S, o = _kda_step(jnp.where(fresh, 0.0, S), q, k, v, g, b, prec)
            out = _kda_output(o, h, w, cfg, prec)
            state = lax.dynamic_update_slice_in_dim(
                state, window[:, 1:].reshape(-1), kd.tail_base, 0)
            state = lax.dynamic_update_slice_in_dim(
                state, S.reshape(-1), kd.s_base, 0)
            return lax.dynamic_update_slice_in_dim(state, out.reshape(-1),
                                                   0, 0)

    return consumer


# -- the model on a facade ----------------------------------------------------

class KimiLinear(ds.DeepSeekV2):
    """Kimi-Linear weights on a one-rank facade, and the decode model
    `serve.DecodeServer` drives: deepseek_v2's MLA, FFN and head
    consumers (NoPE, sigmoid routing, the held experts), the KDA
    consumer, and a prefill that writes each KDA layer's final tails
    and S and each MLA layer's cache rows."""

    def __init__(self, accl, cfg: KimiLinearConfig, **kw):
        if accl.world != 1:
            raise ValueError("Kimi-Linear decodes on a facade of one rank "
                             "(data-parallel attention); got world "
                             f"{accl.world}")
        if kw.get("prefill_chunk", 512) % KDA_CHUNK:
            raise ValueError(f"prefill_chunk must be a multiple of "
                             f"{KDA_CHUNK}")
        super().__init__(accl, cfg, **kw)

    @property
    def layer_counts(self) -> dict:
        mla = sum(map(self.cfg.is_mla, range(self.cfg.num_hidden_layers)))
        return {"kda_layers": self.cfg.num_hidden_layers - mla,
                "mla_layers": mla}

    def _attn_spec(self, layer: int):
        return attn_weights(self.cfg, layer)

    def _states(self, dims):
        kd = kda_dims(self.cfg, dims.batch)
        return [(dims.n_state, "mla_cache") if self.cfg.is_mla(l)
                else (kd.n_state, "kda_state")
                for l in range(self.cfg.num_hidden_layers)]

    def _attn_consumer(self, layer: int, dims):
        if self.cfg.is_mla(layer):
            return ds.make_attn_consumer(self.cfg, dims, self.precision,
                                         rope=False)
        return make_kda_consumer(self.cfg, kda_dims(self.cfg, dims.batch),
                                 self.precision)

    def _prefill_program(self, dims):
        """One chunk of one slot's positions through every layer: KDA
        layers write the slot's tails and S after the chunk's tokens
        (from zero at position 0), MLA layers the chunk's cache rows;
        the states are donated, so updated in place."""
        accl, cfg, prec = self.accl, self.cfg, self.precision
        axis = accl.axis_name
        D, T, W = dims.d_model, dims.max_len, dims.cache_width
        kd = kda_dims(cfg, dims.batch)
        L = cfg.num_hidden_layers
        C = self.prefill_chunk
        attn_names = [ds._names(attn_weights(cfg, l)) for l in range(L)]
        ffn_names = [ds._names(ds._ffn_weights(cfg, l)) for l in range(L)]

        def body(x, where, *flat):
            x = x.reshape(C, D)
            slot, p0, n_valid = where[0, 0], where[0, 1], where[0, 2]
            states = [s.reshape(-1) for s in flat[:L]]
            ops = [o.reshape(-1) for o in flat[L:]]
            pos = p0 + jnp.arange(C)
            out = []
            at = 0
            for l in range(L):
                aw = dict(zip(attn_names[l], ops[at:at + len(attn_names[l])]))
                at += len(attn_names[l])
                fw = dict(zip(ffn_names[l], ops[at:at + len(ffn_names[l])]))
                at += len(ffn_names[l])
                state = states[l]
                if cfg.is_mla(l):
                    base = dims.cache_base + slot * (T * W)
                    rows = lax.dynamic_slice_in_dim(state, base, T * W) \
                        .reshape(T, W)
                    put = {}

                    def write(new, rows=rows, put=put):
                        put["new"] = new
                        return lax.dynamic_update_slice_in_dim(rows, new,
                                                               p0, 0)

                    x = x + ds._mla(x, pos, write, aw, cfg, dims, prec,
                                    rope=False)
                    state = lax.dynamic_update_slice_in_dim(
                        state, put["new"].reshape(-1), base + p0 * W, 0)
                else:
                    t0 = kd.tail_base + slot * kd.tail_size
                    s0 = kd.s_base + slot * kd.s_size
                    fresh = p0 == 0
                    tails = jnp.where(fresh, 0.0, lax.dynamic_slice_in_dim(
                        state, t0, kd.tail_size))
                    S = jnp.where(fresh, 0.0, lax.dynamic_slice_in_dim(
                        state, s0, kd.s_size))
                    with jax.named_scope("kda"):
                        y, tails, S = _kda_prefill(
                            x, tails.reshape(kd.taps - 1, 3, -1),
                            S.reshape(kd.heads, kd.head_dim, kd.head_dim),
                            n_valid, aw, cfg, prec)
                    x = x + y
                    state = lax.dynamic_update_slice_in_dim(
                        state, tails.reshape(-1), t0, 0)
                    state = lax.dynamic_update_slice_in_dim(
                        state, S.reshape(-1), s0, 0)
                x = x + ds._ffn(x, fw, cfg, dims, l, axis, prec)
                out.append(state.reshape(1, -1))
            return tuple(out)

        spec = P(axis)
        n_ops = sum(map(len, attn_names)) + sum(map(len, ffn_names))
        fn = jax.shard_map(
            body, mesh=accl.mesh,
            in_specs=(P(), P()) + (spec,) * (L + n_ops),
            out_specs=(spec,) * L, check_vma=False)
        return jax.jit(fn, donate_argnums=tuple(range(2, 2 + L)))

