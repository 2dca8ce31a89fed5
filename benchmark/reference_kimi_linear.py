"""The benchmark's plain reference for Kimi-Linear decode, and the weights
it is compared on.

Imports nothing of the program. A copy of the repository's reference
(accl_tpu/models/kimi_linear_reference.py), so that a change to the
program cannot move the yardstick: the full-sequence forward in
straightforward `jax.numpy`, fp32, under
`jax.default_matmul_precision("highest")`; KDA's gated delta rule token
by token (`lax.scan`) from a zero state; MLA decompressed before
attention with nothing rotated (no cache); sigmoid routing over every
expert, each held expert computed on every token and masked by its
gate, the experts not held adding nothing; nothing sharded. It runs on
one chip, layer by layer, with the histories of a few slots side by
side (padded at the end to a common length, which causal attention and
the recurrence never look back at), MLA's queries in blocks and the
experts one after another, so that one layer's weights and activations
fit.

The weights are regenerated from the seed by the rule the program
documents for its seeded weights: each whole weight is
`normal(fold_in(fold_in(key, layer), tag))` times its std (norm weights
1 + 0.1 normal; `uniform` ones uniform on their range), key from the
seed's 64 bits; the embedding, the final norm and the head count as
layer `num_hidden_layers`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TAGS = {name: i for i, name in enumerate((
    "attn_norm", "wq", "wkva", "kva_norm", "wkvb", "wo", "ffn_norm",
    "w_gate", "w_up", "w_down", "router", "e_gate", "e_up", "e_down",
    "s_gate", "s_up", "s_down", "final_norm", "head", "embed",
    "router_bias", "kda_qkv", "kda_conv", "kda_fa", "kda_fb", "kda_dt_bias",
    "kda_a_log", "kda_b", "kda_ga", "kda_gb", "kda_o_norm", "kda_o"))}
PAD_TO = 1024  # histories are padded to a multiple of this
Q_BLOCK = 1024


def _key(seed: int):
    seed %= 1 << 64
    return jax.random.wrap_key_data(
        jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)))


def is_mla(c: dict, layer: int) -> bool:
    return layer + 1 in c["linear_attn_config"]["full_attn_layers"]


def shapes(c: dict, layer: int) -> list:
    """(name, whole shape, init) of a layer's weights, or of the head's
    when layer == num_hidden_layers."""
    D, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    R, dn, dr, dv = (c["kv_lora_rank"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    lin = c["linear_attn_config"]
    Hk, K, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    F, E = c["moe_intermediate_size"], c["router_experts"]
    held, S = c["num_experts"], c["num_shared_experts"] * F
    norm = ("norm",)

    def n(std):
        return ("normal", std)

    if layer == c["num_hidden_layers"]:
        return [("final_norm", (D,), norm), ("head", (D, V), n(D ** -0.5))]
    if is_mla(c, layer):
        out = [("attn_norm", (D,), norm),
               ("wq", (D, H, dn + dr), n(D ** -0.5)),
               ("wkva", (D, R + dr), n(D ** -0.5)), ("kva_norm", (R,), norm),
               ("wkvb", (R, H, dn + dv), n(R ** -0.5)),
               ("wo", (H, dv, D), n((H * dv) ** -0.5))]
    else:
        HK = Hk * K
        out = [("attn_norm", (D,), norm),
               ("kda_qkv", (D, 3 * HK), n(D ** -0.5)),
               ("kda_conv", (3, taps, HK), n(taps ** -0.5)),
               ("kda_fa", (D, K), n(D ** -0.5)),
               ("kda_fb", (K, HK), n(K ** -0.5)),
               ("kda_dt_bias", (HK,), ("uniform", math.log(math.expm1(1e-3)),
                                       math.log(math.expm1(0.1)))),
               ("kda_a_log", (Hk,), ("uniform", 0.0, math.log(16.0))),
               ("kda_b", (D, Hk), n(D ** -0.5)),
               ("kda_ga", (D, K), n(D ** -0.5)),
               ("kda_gb", (K, HK), n(K ** -0.5)),
               ("kda_o_norm", (K,), norm),
               ("kda_o", (HK, D), n(HK ** -0.5))]
    out.append(("ffn_norm", (D,), norm))
    if layer < c["first_k_dense_replace"]:
        i = c["intermediate_size"]
        return out + [("w_gate", (D, i), n(D ** -0.5)),
                      ("w_up", (D, i), n(D ** -0.5)),
                      ("w_down", (i, D), n(i ** -0.5))]
    return out + [("router", (D, E), n(D ** -0.5)),
                  ("router_bias", (E,), n(0.1)),
                  ("e_gate", (held, D, F), n(D ** -0.5)),
                  ("e_up", (held, D, F), n(D ** -0.5)),
                  ("e_down", (held, F, D), n(F ** -0.5)),
                  ("s_gate", (D, S), n(D ** -0.5)),
                  ("s_up", (D, S), n(D ** -0.5)),
                  ("s_down", (S, D), n(S ** -0.5))]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _weight(key, layer, name, shape, init):
    k = jax.random.fold_in(jax.random.fold_in(key, layer), TAGS[name])
    if init[0] == "uniform":
        return jax.random.uniform(k, shape, jnp.float32, init[1], init[2])
    z = jax.random.normal(k, shape, jnp.float32)
    return 1.0 + 0.1 * z if init[0] == "norm" else z * init[1]


def weights(c: dict, seed: int, layer: int) -> dict:
    key = _key(seed)
    return {name: _weight(key, layer, name, shape, init)
            for name, shape, init in shapes(c, layer)}


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(xs, w, c):
    """KDA over S sequences xs (S, T, D) side by side, token by token."""
    S_, T, _ = xs.shape
    lin = c["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    eps = c["rms_norm_eps"]
    h = _rmsnorm(xs, w["attn_norm"], eps)
    proj = (h @ w["kda_qkv"]).reshape(S_, T, 3, H * K)
    padded = jnp.concatenate([jnp.zeros((S_, taps - 1, 3, H * K)), proj], 1)
    conv = sum(padded[:, j:j + T] * w["kda_conv"][:, j] for j in range(taps))
    conv = jax.nn.silu(conv)
    q = _l2norm(conv[:, :, 0].reshape(S_, T, H, K)) * K ** -0.5
    k = _l2norm(conv[:, :, 1].reshape(S_, T, H, K))
    v = conv[:, :, 2].reshape(S_, T, H, K)
    f = (h @ w["kda_fa"]) @ w["kda_fb"] + w["kda_dt_bias"]
    log_a = -jnp.exp(w["kda_a_log"])[:, None] \
        * jax.nn.softplus(f).reshape(S_, T, H, K)
    beta = jax.nn.sigmoid(h @ w["kda_b"])

    def step(st, t):
        qt, kt, vt, at, bt = t
        st = st * jnp.exp(at)[..., None]
        st = st - bt[..., None, None] * kt[..., None] \
            * jnp.einsum("shk,shkv->shv", kt, st)[:, :, None, :]
        st = st + bt[..., None, None] * kt[..., None] * vt[:, :, None, :]
        return st, jnp.einsum("shkv,shk->shv", st, qt)

    _, o = jax.lax.scan(step, jnp.zeros((S_, H, K, K)), tuple(
        jnp.swapaxes(a, 0, 1) for a in (q, k, v, log_a, beta)))
    o = _rmsnorm(jnp.swapaxes(o, 0, 1), w["kda_o_norm"], eps) \
        .reshape(S_, T, H * K)
    return (o * jax.nn.sigmoid((h @ w["kda_ga"]) @ w["kda_gb"])) @ w["kda_o"]


def _mla(x, w, c):
    """Naive MLA with no rotation over one whole sequence x (T, D)."""
    T = x.shape[0]
    H, dn = c["num_attention_heads"], c["qk_nope_head_dim"]
    R, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    eps = c["rms_norm_eps"]
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = jnp.einsum("td,dhk->thk", h, w["wq"])
    ckv = h @ w["wkva"]
    cl = _rmsnorm(ckv[:, :R], w["kva_norm"], eps)
    kv = jnp.einsum("tc,chk->thk", cl, w["wkvb"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(ckv[:, None, R:], (T, H, dr))], -1)
    v = kv[..., dn:]
    outs = []
    for lo in range(0, T, Q_BLOCK):
        s = jnp.einsum("qhk,thk->hqt", q[lo:lo + Q_BLOCK], k) \
            * (dn + dr) ** -0.5
        causal = (jnp.arange(T)[None, None, :]
                  > (lo + jnp.arange(s.shape[1]))[None, :, None])
        p = jax.nn.softmax(jnp.where(causal, -jnp.inf, s), -1)
        outs.append(jnp.einsum("hqt,thv->qhv", p, v))
    return jnp.einsum("thv,hvd->td", jnp.concatenate(outs, 0), w["wo"])


def _swiglu(h, g, u, d):
    return (jax.nn.silu(h @ g) * (h @ u)) @ d


def _moe(h, w, c):
    """Sigmoid routing over every expert; the held experts on every
    token, masked by their gates; the shared expert."""
    scores = jax.nn.sigmoid(h @ w["router"])
    _, top_i = jax.lax.top_k(scores + w["router_bias"],
                             c["num_experts_per_token"])
    top_p = jnp.take_along_axis(scores, top_i, -1)
    if c["moe_renormalize"]:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * c["routed_scaling_factor"]
    first = c["first_held_expert"]

    def one(y, j):
        gate = jnp.sum(jnp.where(top_i == first + j, top_p, 0.0), -1)
        return y + gate[:, None] * _swiglu(h, w["e_gate"][j], w["e_up"][j],
                                           w["e_down"][j]), None

    y0 = _swiglu(h, w["s_gate"], w["s_up"], w["s_down"])
    y, _ = jax.lax.scan(one, y0, jnp.arange(c["num_experts"]))
    return y


def _layer(xs, w, c, layer):
    """One layer over S sequences xs (S, T, D)."""
    with jax.default_matmul_precision("highest"):
        S, T, D = xs.shape
        if is_mla(c, layer):
            xs = xs + jax.lax.map(lambda x: _mla(x, w, c), xs)
        else:
            xs = xs + _kda(xs, w, c)
        h = _rmsnorm(xs, w["ffn_norm"], c["rms_norm_eps"]).reshape(S * T, D)
        y = (_swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
             if layer < c["first_k_dense_replace"] else _moe(h, w, c))
        return xs + y.reshape(S, T, D)


def _head(x, w, c):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, w["final_norm"], c["rms_norm_eps"]) @ w["head"]


class DecodeReference:
    """The reference forward for the configuration `c` (its JSON dict)
    and the weights of `seed`."""

    def __init__(self, c: dict, seed: int):
        self.c = c
        self.seed = seed
        self._layer = jax.jit(functools.partial(_layer, c=c),
                              static_argnames=("layer",))

    def logits_at(self, histories, positions) -> list:
        """For each history (token ids), the reference's logits at the
        given positions: a list of (len(positions[i]), V) arrays."""
        c, L = self.c, self.c["num_hidden_layers"]
        T = -(-max(len(h) for h in histories) // PAD_TO) * PAD_TO
        toks = np.zeros((len(histories), T), np.int64)
        for i, h in enumerate(histories):
            toks[i, :len(h)] = h
        key = _key(self.seed)
        embed = _weight(key, L, "embed", (c["vocab_size"], c["hidden_size"]),
                        ("normal", 1.0))
        xs = embed[jnp.asarray(toks)]
        del embed
        for layer in range(L):
            w = weights(c, self.seed, layer)
            xs = self._layer(xs, w, layer=layer)
            del w
        w = weights(c, self.seed, L)
        return [np.asarray(_head(xs[i, np.asarray(pos)], w, self.c),
                           np.float64) for i, pos in enumerate(positions)]
