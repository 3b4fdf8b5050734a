"""What ONE train of the ``smallthinker`` cell needs, from the
configuration's shapes and the program's own counters: operations and
bytes of the whole step (for the share of the chip's peak), of the
global layers' attention product (``attention``: every causal pair of
the packing's segments, ``attn_pairs``), of the window layers'
(``window_attention``: the pairs the window leaves,
``attn_pairs_window``) and of the held experts' grouped products — the
same work whatever implements it, never the tiles a kernel visits. A
forward pass costs 2 operations a multiply-add, the backward pass twice
the forward; recomputation is NOT counted.

Also which ``seqrec.*`` scopes the cell's own device metrics sum
(``SCOPES``; the global layers' are ``roofline_lfm2.SCOPES``'s
``gqa_*``, the others ``seq_layers.SCOPES``).
"""

from __future__ import annotations

import scope_layers
import seq_layers

#: metric → the scopes (innermost wins) whose device seconds it sums
SCOPES = {
    "swa_attention": ("seqrec.swa.attention",),
    "swa_proj": ("seqrec.swa",),
}
#: bytes of a matmul operand (bfloat16)
OPERAND = 2


def seconds(obs, metric: str):
    return scope_layers.seconds(obs, *SCOPES[metric])


def roofline_pct(obs, metric: str, part: str):
    return seq_layers.share_pct(obs, seconds(obs, metric), part)


def layers(c) -> dict:
    window = sum(c.sliding_window_layout)
    return {"window": window, "global": c.num_hidden_layers - window}


def per_token_macs(c) -> dict:
    """Multiply-adds per token of one forward pass, by part (the routed
    experts and attention's products are counted from counters, not
    here)."""
    d, L = c.hidden_size, c.num_hidden_layers
    q = c.num_attention_heads * c.head_dim
    kv = c.num_key_value_heads * c.head_dim
    return {
        "attn_proj": (2 * d * q + 2 * d * kv) * L,
        "router": d * c.router_experts * L,
        "head": d * c.vocab_size,
    }


def needs(c, fit: dict, pack: dict) -> dict:
    """``fit``: the ``seqrec.fit`` span's attributes of the train
    (``steps``; ``moe_pairs_here`` over all its steps and layers);
    ``pack``: the ``seqrec.pack`` span's (``sequences``,
    ``real_tokens``, ``attn_pairs`` and ``attn_pairs_window`` of one
    epoch and head)."""
    epochs = fit["steps"] * c.seqs_per_step / max(pack["sequences"], 1)
    tokens = pack["real_tokens"] * epochs
    d, H, Hkv, D = (c.hidden_size, c.num_attention_heads,
                    c.num_key_value_heads, c.head_dim)
    n = layers(c)
    expert_macs = fit["moe_pairs_here"] * 3 * d * c.moe_ffn_hidden_size
    # a (query, key) pair costs a head D multiply-adds for its score
    # and D for its value
    pair_macs = epochs * H * 2 * D
    global_macs = pack["attn_pairs"] * n["global"] * pair_macs
    window_macs = pack["attn_pairs_window"] * n["window"] * pair_macs
    dense_macs = sum(per_token_macs(c).values()) * tokens
    # attention moves q, k, v and its output once forward, and them
    # with their cotangents backward
    layer_bytes = 3 * tokens * OPERAND * D * 2 * (H + Hkv)
    # the experts' weights are read forward and backward and their
    # gradients written; each pair's row goes in and out of each of
    # the three products, forward and backward
    weight_bytes = (3 * fit["steps"] * c.num_hidden_layers
                    * c.moe_num_primary_experts * 3 * d
                    * c.moe_ffn_hidden_size * OPERAND)
    row_bytes = 3 * fit["moe_pairs_here"] * OPERAND * (
        2 * d + 4 * c.moe_ffn_hidden_size)
    return {
        "train_flops": 3 * 2 * (dense_macs + expert_macs + global_macs
                                + window_macs),
        "attention": {"flops": 3 * 2 * global_macs,
                      "bytes": layer_bytes * n["global"]},
        "window_attention": {"flops": 3 * 2 * window_macs,
                             "bytes": layer_bytes * n["window"]},
        "experts": {"flops": 3 * 2 * expert_macs,
                    "bytes": weight_bytes + row_bytes},
    }
