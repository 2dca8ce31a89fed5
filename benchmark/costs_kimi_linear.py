"""Operations and bytes of a Kimi-Linear decode step on its chip,
computed from its shapes: the yardstick for `step_mfu.kimi` and
`kda_roofline.kimi`.

A step decodes one token for each of B slots; in the MLA layers slot i
attends over its pos_i + 1 filled cache rows, and `context` is their sum
over the slots. Model operations count multiply-adds as 2, whatever
precision the chip computes them in, of the work this chip's share is
for: every head of attention, the dense and shared FFNs, the head over
the vocabulary slice, and of the routed experts the share an even
routing sends to the experts held here (k x held / E of a token's k
choices). Bytes are fp32 (4 a number).
"""

from __future__ import annotations

import costs_mla_moe

F32 = 4


def _kda_dims(c: dict):
    lin = c["linear_attn_config"]
    return (c["hidden_size"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"])


def is_mla(c: dict, layer: int) -> bool:
    return layer + 1 in c["linear_attn_config"]["full_attn_layers"]


def kda_layers(c: dict) -> int:
    return sum(not is_mla(c, l) for l in range(c["num_hidden_layers"]))


def kda_flops(c: dict, tokens: int) -> float:
    """One KDA layer for `tokens` tokens: the q, k, v projections, the
    short conv, the decay and output gates (rank K), b, the recurrence
    (decay, k^T S, the rank-1 update, S^T q) and W_o."""
    D, H, K, taps = _kda_dims(c)
    HK = H * K
    per_token = (2 * D * 3 * HK + 2 * taps * 3 * HK
                 + 2 * (2 * D * K + 2 * K * HK) + 2 * D * H
                 + 7 * H * K * K + 2 * HK * D)
    return tokens * per_token


def kda_weight_numbers(c: dict) -> int:
    D, H, K, taps = _kda_dims(c)
    HK = H * K
    return (D + D * 3 * HK + 3 * taps * HK + 2 * (D * K + K * HK) + HK + H
            + D * H + K + HK * D)


def kda_state_numbers(c: dict, slots: int) -> int:
    """One KDA layer's state of `slots` slots: the conv tails and S."""
    _, H, K, taps = _kda_dims(c)
    return slots * ((taps - 1) * 3 * H * K + H * K * K)


def kda_part(c: dict, tokens: int):
    """(operations, HBM bytes) of the step's KDA consumers: every KDA
    layer's tails and S read and written, and its weights read."""
    n = kda_layers(c)
    nbytes = n * F32 * (2 * kda_state_numbers(c, tokens)
                        + kda_weight_numbers(c))
    return n * kda_flops(c, tokens), nbytes


def ffn_flops(c: dict, layer: int, tokens: int) -> float:
    D = c["hidden_size"]
    if layer < c["first_k_dense_replace"]:
        return tokens * 6 * D * c["intermediate_size"]
    F, E = c["moe_intermediate_size"], c["router_experts"]
    routed = c["num_experts_per_token"] * c["num_experts"] / E
    return tokens * (2 * D * E + routed * 6 * D * F
                     + 6 * D * c["num_shared_experts"] * F)


def step_flops(c: dict, tokens: int, context: int) -> float:
    """Model operations of one decode step on the chip."""
    total = tokens * 2 * c["hidden_size"] * c["vocab_size"]
    for l in range(c["num_hidden_layers"]):
        total += (costs_mla_moe.attn_flops(c, tokens, context,
                                           c["num_attention_heads"])
                  if is_mla(c, l) else kda_flops(c, tokens))
        total += ffn_flops(c, l, tokens)
    return total

