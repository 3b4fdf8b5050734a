"""What ONE train of the sequence cell needs, from the configuration's
shapes and the program's own counters: operations and bytes of the
whole step (for the share of the chip's peak), of the attention
product and of the held experts' grouped products (for their
rooflines). A forward pass costs 2 operations a multiply-add, the
backward pass twice the forward; recomputation is NOT counted.
"""

from __future__ import annotations


def per_token_macs(c) -> dict:
    """Multiply-adds per token of one forward pass, by part (the
    routed experts and attention's product are counted from counters,
    not here)."""
    d, H = c.hidden_size, c.num_attention_heads
    proj = (d * c.q_lora_rank + c.q_lora_rank * H * c.qk_head_dim
            + d * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank * H * (c.qk_nope_head_dim + c.v_head_dim)
            + H * c.v_head_dim * d)
    shared = 3 * d * c.n_shared_experts * c.moe_intermediate_size
    router = d * c.router_experts
    n_moe = c.n_moe_layers + c.num_nextn_predict_layers
    return {
        "mla_proj": proj * (c.num_hidden_layers
                            + c.num_nextn_predict_layers),
        "dense_ffn": 3 * d * c.intermediate_size * c.first_k_dense_replace,
        "shared_router": (shared + router) * n_moe,
        "heads": d * c.vocab_size * (1 + c.num_nextn_predict_layers),
        "mtp_proj": 2 * d * d * c.num_nextn_predict_layers,
    }


def needs(c, fit: dict, pack: dict) -> dict:
    """``fit``: the ``seqrec.fit`` span's attributes of the train
    (``steps``; ``moe_pairs_here`` over all its steps and layers);
    ``pack``: the ``seqrec.pack`` span's (``sequences``,
    ``real_tokens``, ``attn_pairs`` of one epoch)."""
    epochs = fit["steps"] * c.seqs_per_step / max(pack["sequences"], 1)
    tokens = pack["real_tokens"] * epochs
    n_attn = c.num_hidden_layers + c.num_nextn_predict_layers
    H = c.num_attention_heads
    expert_macs = fit["moe_pairs_here"] * 3 * c.hidden_size \
        * c.moe_intermediate_size
    attn_macs = (pack["attn_pairs"] * epochs * n_attn * H
                 * (c.qk_head_dim + c.v_head_dim))
    dense_macs = sum(per_token_macs(c).values()) * tokens
    # attention moves q, k, v and its output once forward, and them
    # with their cotangents backward, in the matmul dtype (2 B)
    attn_bytes = 3 * tokens * n_attn * H * 2 * (
        2 * c.qk_head_dim + 2 * c.v_head_dim)
    # the experts' weights are read forward and backward and their
    # gradients written; each pair's row goes in and out of each of
    # the three products, forward and backward
    steps_layers = fit["steps"] * (c.n_moe_layers
                                   + c.num_nextn_predict_layers)
    weight_bytes = (3 * steps_layers * c.n_routed_experts * 3
                    * c.hidden_size * c.moe_intermediate_size * 2)
    row_bytes = 3 * fit["moe_pairs_here"] * 2 * (
        2 * c.hidden_size + 4 * c.moe_intermediate_size)
    return {
        "train_flops": 3 * 2 * (dense_macs + expert_macs + attn_macs),
        "attention": {"flops": 3 * 2 * attn_macs, "bytes": attn_bytes},
        "experts": {"flops": 3 * 2 * expert_macs,
                    "bytes": weight_bytes + row_bytes},
    }
