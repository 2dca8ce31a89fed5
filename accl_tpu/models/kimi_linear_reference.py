"""The plain reference of Kimi-Linear: the full-sequence forward in
straightforward `jax.numpy`, fp32, under
`jax.default_matmul_precision("highest")`.

It follows the Kimi Linear paper (arXiv:2510.26692), the published
config.json and the `fla` library's KimiDeltaAttention, as kimi_linear's
docstring writes them out: pre-norm layers; KDA layers with a causal
depthwise short convolution and SiLU on the q, k, v projections, L2-
normalized q (times dk^-0.5) and k, a per-channel decay log a = -exp(
A_log) softplus(h W_fa W_fb + dt_bias), b = sigmoid(h W_b), the gated
delta rule S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T run token by
token (`lax.scan`), o = S^T q, a per-head RMSNorm of o gated by
sigmoid(h W_ga W_gb), then W_o; MLA layers decompressed before attention
with nothing rotated (NoPE), scale (dn + dr)^-0.5, causal softmax; a
dense SwiGLU first layer; MoE layers with sigmoid scores, top-k of the
scores plus the score-correction bias, the chosen scores renormalized
over the k and times the routed scale, one shared expert; a final
RMSNorm and an untied head.

Departures, each by design: no cache and no batching (one sequence,
whole); every held routed expert is computed on every token and masked
by its gate, and the experts the configuration does not hold add
nothing (as on the chip); nothing is sharded. Weights come in whole, a dict a
layer under the names and shapes of kimi_linear's weight lists. Imports
nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rmsnorm(x, g, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda(x, lyr, cfg):
    """KDA over the whole sequence x (T, D), token by token."""
    T = x.shape[0]
    H, K = cfg.kda_num_heads, cfg.kda_head_dim
    taps = cfg.short_conv_kernel_size
    h = rmsnorm(x, lyr["attn_norm"], cfg.rms_norm_eps)
    proj = (h @ lyr["kda_qkv"]).reshape(T, 3, H * K)
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3, H * K)), proj], 0)
    conv = jnp.zeros((T, 3, H * K))
    for j in range(taps):  # tap j multiplies the input t - (taps-1) + j
        conv = conv + padded[j:j + T] * lyr["kda_conv"][:, j]
    conv = jax.nn.silu(conv)
    q = l2norm(conv[:, 0].reshape(T, H, K)) * K ** -0.5
    k = l2norm(conv[:, 1].reshape(T, H, K))
    v = conv[:, 2].reshape(T, H, K)
    f = (h @ lyr["kda_fa"]) @ lyr["kda_fb"] + lyr["kda_dt_bias"]
    log_a = -jnp.exp(lyr["kda_a_log"])[:, None] \
        * jax.nn.softplus(f).reshape(T, H, K)
    beta = jax.nn.sigmoid(h @ lyr["kda_b"])

    def step(S, t):
        qt, kt, vt, at, bt = t
        S = S * jnp.exp(at)[..., None]  # Diag(a) S, per key channel
        S = S - bt[:, None, None] * kt[..., None] \
            * jnp.einsum("hk,hkv->hv", kt, S)[:, None, :]  # (I - b k k^T)
        S = S + bt[:, None, None] * kt[..., None] * vt[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    _, o = jax.lax.scan(step, jnp.zeros((H, K, K)), (q, k, v, log_a, beta))
    o = rmsnorm(o, lyr["kda_o_norm"], cfg.rms_norm_eps).reshape(T, H * K)
    gate = jax.nn.sigmoid((h @ lyr["kda_ga"]) @ lyr["kda_gb"])
    return (o * gate) @ lyr["kda_o"]


def mla(x, lyr, cfg):
    """Naive MLA with no rotation over the whole sequence x (T, D)."""
    T = x.shape[0]
    H, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim
    R, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    h = rmsnorm(x, lyr["attn_norm"], cfg.rms_norm_eps)
    q = jnp.einsum("td,dhk->thk", h, lyr["wq"])
    ckv = h @ lyr["wkva"]
    c = rmsnorm(ckv[:, :R], lyr["kva_norm"], cfg.rms_norm_eps)
    kv = jnp.einsum("tc,chk->thk", c, lyr["wkvb"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(ckv[:, None, R:], (T, H, dr))], -1)
    v = kv[..., dn:]
    s = jnp.einsum("qhk,thk->hqt", q, k) * (dn + dr) ** -0.5
    causal = jnp.arange(T)[None, None, :] > jnp.arange(T)[None, :, None]
    p = jax.nn.softmax(jnp.where(causal, -jnp.inf, s), -1)
    return jnp.einsum("thv,hvd->td", jnp.einsum("hqt,thv->qhv", p, v),
                      lyr["wo"])


def moe(h, lyr, cfg):
    """Sigmoid routing over every expert; the held experts, each on every
    token, masked by its gate; the shared expert."""
    scores = jax.nn.sigmoid(h @ lyr["router"])
    _, top_i = jax.lax.top_k(scores + lyr["router_bias"],
                             cfg.num_experts_per_tok)
    top_p = jnp.take_along_axis(scores, top_i, -1)
    if cfg.norm_topk_prob:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * cfg.routed_scaling_factor
    y = swiglu(h, lyr["s_gate"], lyr["s_up"], lyr["s_down"])
    for j in range(cfg.held_experts):
        e = cfg.first_held_expert + j
        gate = jnp.sum(jnp.where(top_i == e, top_p, 0.0), -1)
        y = y + gate[:, None] * swiglu(h, lyr["e_gate"][j], lyr["e_up"][j],
                                       lyr["e_down"][j])
    return y


def ffn(x, lyr, cfg, layer: int):
    h = rmsnorm(x, lyr["ffn_norm"], cfg.rms_norm_eps)
    if layer < cfg.first_k_dense_replace:
        return swiglu(h, lyr["w_gate"], lyr["w_up"], lyr["w_down"])
    return moe(h, lyr, cfg)


def layer_forward(x, lyr, cfg, layer: int):
    """One pre-norm layer over the whole sequence x (T, D)."""
    with jax.default_matmul_precision("highest"):
        attn = mla if layer + 1 in cfg.full_attn_layers else kda
        x = x + attn(x, lyr, cfg)
        return x + ffn(x, lyr, cfg, layer)


def forward(params, tokens, cfg):
    """Logits (T, V) of one sequence of token ids (T,)."""
    x = jnp.asarray(params["embed"])[jnp.asarray(tokens)]
    for i, lyr in enumerate(params["layers"]):
        x = layer_forward(x, lyr, cfg, i)
    with jax.default_matmul_precision("highest"):
        return rmsnorm(x, params["final_norm"], cfg.rms_norm_eps) \
            @ params["head"]
