"""What ONE train of the ``lfm2_moe`` cell needs, from the
configuration's shapes and the program's own counters: operations and
bytes of the whole step (for the share of the chip's peak), of the
attention product, of the held experts' grouped products and of the
short convolution's gates and taps (for their rooflines) — the same
work whatever implements it. A forward pass costs 2 operations a
multiply-add, the backward pass twice the forward; recomputation is
NOT counted.

Also which ``seqrec.*`` scopes the cell's own device metrics sum
(``SCOPES``; the others read ``seq_layers.SCOPES``).
"""

from __future__ import annotations

import scope_layers
import seq_layers

#: metric → the scopes (innermost wins) whose device seconds it sums
SCOPES = {
    "shortconv": ("seqrec.conv.mix",),
    "shortconv_proj": ("seqrec.conv",),
    "gqa_attention": ("seqrec.gqa.attention",),
    "gqa_proj": ("seqrec.gqa",),
}
#: bytes of a matmul operand (bfloat16)
OPERAND = 2
#: operations a row and channel of the gates and taps costs forward:
#: B ⊙ u, three taps' multiply-adds, C ⊙ c
MIX_FLOPS = 8


def seconds(obs, metric: str):
    return scope_layers.seconds(obs, *SCOPES[metric])


def roofline_pct(obs, metric: str, part: str):
    return seq_layers.share_pct(obs, seconds(obs, metric), part)


def layers(c) -> dict:
    conv = sum(op == "conv" for op in c.layer_types)
    return {"conv": conv, "attn": c.num_hidden_layers - conv,
            "dense": c.num_dense_layers, "moe": c.n_moe_layers}


def per_token_macs(c) -> dict:
    """Multiply-adds per token of one forward pass, by part (the routed
    experts and attention's product are counted from counters, not
    here)."""
    d, n = c.hidden_size, layers(c)
    kv = c.num_key_value_heads * c.head_dim
    return {
        "conv_proj": (d * 3 * d + d * d) * n["conv"],
        "gqa_proj": (2 * d * d + 2 * d * kv) * n["attn"],
        "dense_ffn": 3 * d * c.intermediate_size * n["dense"],
        "router": d * c.router_experts * n["moe"],
        "head": d * c.vocab_size,
    }


def needs(c, fit: dict, pack: dict) -> dict:
    """``fit``: the ``seqrec.fit`` span's attributes of the train
    (``steps``; ``moe_pairs_here`` over all its steps and layers);
    ``pack``: the ``seqrec.pack`` span's (``sequences``,
    ``real_tokens``, ``attn_pairs`` of one epoch)."""
    epochs = fit["steps"] * c.seqs_per_step / max(pack["sequences"], 1)
    tokens = pack["real_tokens"] * epochs
    d, H, D, n = c.hidden_size, c.num_attention_heads, c.head_dim, layers(c)
    expert_macs = fit["moe_pairs_here"] * 3 * d * c.moe_intermediate_size
    # a (query, key) pair costs a head D multiply-adds for its score
    # and D for its value
    attn_macs = pack["attn_pairs"] * epochs * n["attn"] * H * 2 * D
    dense_macs = sum(per_token_macs(c).values()) * tokens
    # attention moves q, k, v and its output once forward, and them
    # with their cotangents backward
    attn_bytes = 3 * tokens * n["attn"] * OPERAND * D * 2 * (
        H + c.num_key_value_heads)
    # the experts' weights are read forward and backward and their
    # gradients written; each pair's row goes in and out of each of
    # the three products, forward and backward
    weight_bytes = (3 * fit["steps"] * n["moe"] * c.num_experts * 3 * d
                    * c.moe_intermediate_size * OPERAND)
    row_bytes = 3 * fit["moe_pairs_here"] * OPERAND * (
        2 * d + 4 * c.moe_intermediate_size)
    # gates and taps: B, C, u read and y written forward (4 d a row);
    # y's cotangent and B, C, u read, their three cotangents written
    # backward (7 d a row) — as matmul operands
    mix_rows = tokens * n["conv"]
    return {
        "train_flops": 3 * 2 * (dense_macs + expert_macs + attn_macs),
        "attention": {"flops": 3 * 2 * attn_macs, "bytes": attn_bytes},
        "experts": {"flops": 3 * 2 * expert_macs,
                    "bytes": weight_bytes + row_bytes},
        "shortconv": {"flops": 3 * MIX_FLOPS * d * mix_rows,
                      "bytes": 11 * d * OPERAND * mix_rows},
    }
