"""What ONE train of the ``qwen3_next`` cell needs, from the
configuration's shapes and the program's own counters: operations and
bytes of the whole step (for the share of the chip's peak), of the full
layer's attention product (``attention``: every causal pair of the
packing's segments, ``attn_pairs``), of the held experts' grouped
products and of the linear layers' RECURRENCE (``gdn_scan``) — the same
work whatever implements it, never a chunked form's products or a
kernel's tiles. A forward pass costs 2 operations a multiply-add, the
backward pass twice the forward; recomputation is NOT counted.

The recurrence, a real row, value head and linear layer: the decay of
the d_k × d_v state (d_k·d_v operations) and three d_k × d_v
multiply-adds (Sᵀk, k δᵀ, Sᵀq): 7·d_k·d_v operations forward. Its
bytes: q, k, v, g, β and o moved once forward, and them with their
cotangents backward.

Also which ``seqrec.*`` scopes the cell's own device metrics sum
(``SCOPES``; the full layer's are ``roofline_lfm2.SCOPES``'s ``gqa_*``,
the others ``seq_layers.SCOPES``).
"""

from __future__ import annotations

import scope_layers
import seq_layers

#: metric → the scopes (innermost wins) whose device seconds it sums
SCOPES = {
    "gdn_scan": ("seqrec.gdn.scan",),
    "gdn_conv": ("seqrec.gdn.conv",),
    "gdn_proj": ("seqrec.gdn",),
}
#: bytes of a matmul operand (bfloat16), and of a decay or a write
#: strength (float32)
OPERAND = 2
GATE = 4
#: operations of the recurrence a row, value head and state element,
#: forward: the decay, and a multiply-add each for Sᵀk, k δᵀ and Sᵀq
SCAN_FLOPS = 7


def seconds(obs, metric: str):
    return scope_layers.seconds(obs, *SCOPES[metric])


def roofline_pct(obs, metric: str, part: str):
    return seq_layers.share_pct(obs, seconds(obs, metric), part)


def layers(c) -> dict:
    full = sum((i + 1) % c.full_attention_interval == 0
               for i in range(c.num_hidden_layers))
    return {"linear": c.num_hidden_layers - full, "full": full}


def per_token_macs(c) -> dict:
    """Multiply-adds per token of one forward pass, by part (the routed
    experts, attention's products and the recurrence are counted from
    counters, not here)."""
    d, L, n = c.hidden_size, c.num_hidden_layers, layers(c)
    keys = c.linear_num_key_heads * c.linear_key_head_dim
    values = c.linear_num_value_heads * c.linear_value_head_dim
    q = c.num_attention_heads * c.head_dim
    kv = c.num_key_value_heads * c.head_dim
    return {
        "gdn_proj": (d * (2 * keys + 2 * values)
                     + d * 2 * c.linear_num_value_heads
                     + values * d) * n["linear"],
        "gdn_conv": (c.linear_conv_kernel_dim * (2 * keys + values)
                     * n["linear"]),
        "attn_proj": (d * 2 * q + 2 * d * kv + q * d) * n["full"],
        "router": d * c.router_experts * L,
        "shared": (3 * d * c.shared_expert_intermediate_size + d) * L,
        "head": d * c.vocab_size,
    }


def needs(c, fit: dict, pack: dict) -> dict:
    """``fit``: the ``seqrec.fit`` span's attributes of the train
    (``steps``; ``moe_pairs_here`` over all its steps and layers);
    ``pack``: the ``seqrec.pack`` span's (``sequences``,
    ``real_tokens`` and ``attn_pairs`` of one epoch and head)."""
    epochs = fit["steps"] * c.seqs_per_step / max(pack["sequences"], 1)
    tokens = pack["real_tokens"] * epochs
    d, H, Hkv, D = (c.hidden_size, c.num_attention_heads,
                    c.num_key_value_heads, c.head_dim)
    Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv, n = c.linear_key_head_dim, c.linear_value_head_dim, layers(c)
    expert_macs = fit["moe_pairs_here"] * 3 * d * c.moe_intermediate_size
    # a (query, key) pair costs a head D multiply-adds for its score
    # and D for its value
    attn_macs = pack["attn_pairs"] * epochs * n["full"] * H * 2 * D
    dense_macs = sum(per_token_macs(c).values()) * tokens
    scan_flops = SCAN_FLOPS * dk * dv * Hv * n["linear"] * tokens
    # attention moves q, k, v and its output once forward, and them
    # with their cotangents backward
    attn_bytes = 3 * tokens * OPERAND * D * 2 * (H + Hkv) * n["full"]
    scan_bytes = 3 * tokens * n["linear"] * (
        OPERAND * (2 * Hk * dk + 2 * Hv * dv) + GATE * 2 * Hv)
    # the experts' weights are read forward and backward and their
    # gradients written; each pair's row goes in and out of each of
    # the three products, forward and backward
    weight_bytes = (3 * fit["steps"] * c.num_hidden_layers * c.num_experts
                    * 3 * d * c.moe_intermediate_size * OPERAND)
    row_bytes = 3 * fit["moe_pairs_here"] * OPERAND * (
        2 * d + 4 * c.moe_intermediate_size)
    return {
        "train_flops": 3 * (2 * (dense_macs + expert_macs + attn_macs)
                            + scan_flops),
        "attention": {"flops": 3 * 2 * attn_macs, "bytes": attn_bytes},
        "experts": {"flops": 3 * 2 * expert_macs,
                    "bytes": weight_bytes + row_bytes},
        "gdn_scan": {"flops": 3 * scan_flops, "bytes": scan_bytes},
    }
