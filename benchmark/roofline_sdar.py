"""What ONE train of the ``sdar_moe`` cell needs, from the
configuration's shapes and the program's own counters: operations and
bytes of the whole step (for the share of the chip's peak), of
attention under the block rule (``bd_attention``: every pair the four
visibility lines leave of the packing's segments, both streams,
``attn_pairs_bd``) and of the held experts' grouped products — the same
work whatever implements it, never the tiles a kernel visits. A forward
pass costs 2 operations a multiply-add, the backward pass twice the
forward; recomputation is NOT counted.

Block diffusion sends BOTH streams' rows (clean and noised: 2 × the
real events) through every layer's projections, router and experts; the
head is needed on the MASKED rows only (``bd_masked``): the least the
mathematics asks, whatever the program computes.

Also which ``seqrec.*`` scopes the cell's own device metrics sum
(``SCOPES``; the others are ``seq_layers.SCOPES``).
"""

from __future__ import annotations

import scope_layers
import seq_layers

#: metric → the scopes (innermost wins) whose device seconds it sums
SCOPES = {
    "bd_attention": ("seqrec.bd.attention",),
    "bd_proj": ("seqrec.bd",),
    "bd_noise": ("seqrec.bd.noise",),
}
#: bytes of a matmul operand (bfloat16)
OPERAND = 2


def seconds(obs, metric: str):
    return scope_layers.seconds(obs, *SCOPES[metric])


def roofline_pct(obs, metric: str, part: str):
    return seq_layers.share_pct(obs, seconds(obs, metric), part)


def per_row_macs(c) -> dict:
    """Multiply-adds per stream row of one forward pass through the
    layers, by part (the routed experts and attention's products are
    counted from counters, not here)."""
    d, L = c.hidden_size, c.num_hidden_layers
    q = c.num_attention_heads * c.head_dim
    kv = c.num_key_value_heads * c.head_dim
    return {"attn_proj": (2 * d * q + 2 * d * kv) * L,
            "router": d * c.router_experts * L}


def needs(c, fit: dict, pack: dict) -> dict:
    """``fit``: the ``seqrec.fit`` span's attributes of the train
    (``steps``; ``moe_pairs_here`` and ``bd_masked`` over all its steps);
    ``pack``: the ``seqrec.pack`` span's (``sequences``,
    ``real_tokens`` and ``attn_pairs_bd`` of one epoch and head)."""
    epochs = fit["steps"] * c.seqs_per_step / max(pack["sequences"], 1)
    rows = 2 * pack["real_tokens"] * epochs
    d, H, Hkv, D, L = (c.hidden_size, c.num_attention_heads,
                       c.num_key_value_heads, c.head_dim,
                       c.num_hidden_layers)
    expert_macs = fit["moe_pairs_here"] * 3 * d * c.moe_intermediate_size
    # a (query, key) pair costs a head D multiply-adds for its score
    # and D for its value
    attn_macs = pack["attn_pairs_bd"] * epochs * L * H * 2 * D
    dense_macs = (sum(per_row_macs(c).values()) * rows
                  + d * c.vocab_size * fit["bd_masked"])
    # attention moves q, k, v and its output once forward, and them
    # with their cotangents backward
    layer_bytes = 3 * rows * OPERAND * D * 2 * (H + Hkv)
    # the experts' weights are read forward and backward and their
    # gradients written; each pair's row goes in and out of each of
    # the three products, forward and backward
    weight_bytes = (3 * fit["steps"] * L * c.num_experts * 3 * d
                    * c.moe_intermediate_size * OPERAND)
    row_bytes = 3 * fit["moe_pairs_here"] * OPERAND * (
        2 * d + 4 * c.moe_intermediate_size)
    return {
        "train_flops": 3 * 2 * (dense_macs + expert_macs + attn_macs),
        "bd_attention": {"flops": 3 * 2 * attn_macs,
                         "bytes": layer_bytes * L},
        "experts": {"flops": 3 * 2 * expert_macs,
                    "bytes": weight_bytes + row_bytes},
    }
