"""A ``glm4_moe_lite`` block stack as the ``sequentialrec`` backbone.

The item catalog takes the place of the token vocabulary. Every
equation is the published block's (GLM-4.7-Flash, ``config.json``,
``model_type glm4_moe_lite``; DeepSeek-V3's attention, router and
multi-token-prediction module, which the family carries):

- **Block**: x ← x + MLA(RMSNorm(x)); x ← x + FFN(RMSNorm(x)). The
  first ``first_k_dense_replace`` layers have a dense SwiGLU, every
  later one the sparse-expert FFN.
- **MLA**: c_q = RMSNorm(x W_qa); q = c_q W_qb → heads × (nope + rope).
  [c_kv ; k_r] = x W_kva; c_kv ← RMSNorm(c_kv); [k_nope ; v] = c_kv
  W_kvb. k_r is ONE rotary key shared by all heads. RoPE on q's rope
  dims and on k_r, positions counted from the start of each segment.
  scores = (q_nope·k_nope + q_rope·k_r)/√(nope + rope), causal AND
  inside one segment. Training needs no latent cache and no weight
  absorption: k_nope and v are expanded for every position, once.
- **MoE**: s = sigmoid(x W_r) in float32 over the router's
  ``n_routed_experts × ep_size`` experts; top-k of s + b, b a
  selection-only bias without gradient; gates scaling · s_e/Σ s; plus
  the shared expert. THIS chip holds experts ``ep_rank·n …
  (ep_rank+1)·n − 1`` and adds only their part
  (:mod:`predictionio_tpu.ops.moe_dispatch`). After each step b moves
  against the load: b_e ← b_e + γ·sign(mean load − load_e).
- **Head**: RMSNorm(x_L) W_head, untied from the embedding.
- **MTP**: h'_i = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L,i)], one
  more block, its own final norm, the SAME embedding and head; it
  predicts t_{i+2}, never across a segment's end. Loss = CE(next) +
  λ·CE_MTP, each a mean over its real targets. The module's block has
  the expert layers' shape, so its weights are the LAST slice of the
  ``moe`` stack and it runs as the last turn of their scanned body
  (one compiled body for all of them).

Precision: float32 master parameters, gradients, Adam moments and
residual stream; matmul operands ``matmul_dtype`` (bfloat16) with
float32 accumulation; router scores, softmax, the norms' statistics
and the loss in float32.

Histories are PACKED into ``seq_len``-slot sequences with segment ids
(:func:`pack_histories`); id 0 is PAD. Packing, the pieces of a block
that any backbone has (``_mm``, ``_rms``, ``_rope``, ``_swiglu``,
``_moe``, ``_chunked_ce``, ``_cast_in_loop``), the train step and the
verb's spans are :mod:`predictionio_tpu.models.seq_backbone`'s; what is
THIS block's is here, and the file ends in its declaration
(:func:`seq_backbone.build` makes the rest of it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Dict

import numpy as np

from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models.seq_backbone import (  # noqa: F401 — the
    # names this module had before the pieces moved, kept for its callers
    _attn_tiles, _cast_in_loop, _chunked_ce, _dt, _is_shape, _mm,
    _moe, _path_name, _rms, _rope, _stacked, _swiglu, _swiglu_shapes,
    pack_histories, scope)


@dataclass(frozen=True)
class GlmConfig(seq_backbone.ArchitectureConfig):
    model_type: ClassVar[str] = "glm4_moe_lite"
    #: what the published config may say and this file can honour
    _REQUIRED: ClassVar[Dict[str, Any]] = {
        "hidden_act": "silu", "attention_bias": False, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc", "rope_scaling": None,
        "tie_word_embeddings": False, "partial_rotary_factor": 1,
        "model_type": "glm4_moe_lite"}
    #: published keys that size nothing here (a limit, not a shape)
    _UNUSED: ClassVar[tuple] = ("max_position_embeddings",)
    _HELD: ClassVar[str] = "n_routed_experts"
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    #: routed experts HELD here; the router is ``ep_size`` times as wide
    n_routed_experts: int = 64
    ep_size: int = 1
    ep_rank: int = 0
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 47
    num_nextn_predict_layers: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    vocab_size: int = 154880
    # -- the training job (not in the published config) ----------------
    seq_len: int = 4096
    seqs_per_step: int = 4
    bias_update_rate: float = 1e-3
    mtp_loss_weight: float = 0.3
    clip_norm: float = 1.0
    init_std: float = 0.02
    matmul_dtype: str = "bfloat16"
    #: most query rows an attention tile holds; tokens per chunk of a
    #: SwiGLU and of the loss: what bounds the program's temporaries
    attn_block: int = 512
    token_chunk: int = 4096

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]) -> "GlmConfig":
        c = super().from_architecture(arch)
        if arch.get("num_key_value_heads",
                    arch.get("num_attention_heads")) != arch.get(
                        "num_attention_heads"):
            raise ValueError("latent attention has one key per head")
        if arch.get("num_nextn_predict_layers", 1) != 1:
            raise ValueError("exactly one MTP module is implemented")
        return c

    @classmethod
    def known_keys(cls) -> frozenset:
        # latent attention has no key-value heads; the key may say so
        return super().known_keys() | {"num_key_value_heads"}

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # what :func:`_mla` reads of a config beside its sizes; a family
    # with scaled positions (``xing4_0``: YaRN) overrides both
    @property
    def rope_freqs(self):
        """The rope half's frequencies; None: θ^(−i/half)."""
        return None

    @property
    def softmax_scale(self) -> float:
        return 1.0 / np.sqrt(self.qk_head_dim)


# -- parameters ---------------------------------------------------------------


def _attn_shapes(c: GlmConfig) -> Dict[str, tuple]:
    d, H = c.hidden_size, c.num_attention_heads
    return {"wqa": (d, c.q_lora_rank), "q_norm": (c.q_lora_rank,),
            "wqb": (c.q_lora_rank, H * c.qk_head_dim),
            "wkva": (d, c.kv_lora_rank + c.qk_rope_head_dim),
            "kv_norm": (c.kv_lora_rank,),
            "wkvb": (c.kv_lora_rank,
                     H * (c.qk_nope_head_dim + c.v_head_dim)),
            "wo": (H * c.v_head_dim, d)}


def _block_shapes(c: GlmConfig, dense: bool) -> Dict[str, Any]:
    d = c.hidden_size
    out = {"attn_norm": (d,), "attn": _attn_shapes(c), "ffn_norm": (d,)}
    if dense:
        out["ffn"] = _swiglu_shapes(d, c.intermediate_size)
    else:
        out["router"] = (d, c.router_experts)
        out["experts"] = _swiglu_shapes(d, c.moe_intermediate_size,
                                        (c.n_routed_experts,))
        out["shared"] = _swiglu_shapes(
            d, c.n_shared_experts * c.moe_intermediate_size)
    return out


def param_shapes(c: GlmConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``dense`` and ``moe`` carry a
    leading layer axis: the identical layers are ONE scanned body."""
    d = c.hidden_size
    return {
        "embed": (c.vocab_size, d),
        "dense": _stacked(_block_shapes(c, True), c.first_k_dense_replace),
        # the expert layers, then the MTP module's block
        "moe": _stacked(_block_shapes(c, False), c.n_moe_layers + 1),
        "final_norm": (d,),
        "head": (d, c.vocab_size),
        "mtp": {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                "final_norm": (d,)},
    }


def group_of(name: str) -> str:
    """The parameter group a leaf's gradient norm is recorded under
    (the ``moe`` stack's last slice, the MTP module's block, goes
    under ``mtp.*``: :func:`group_squares`)."""
    parts = name.split(".")
    if parts[-1].endswith("norm"):
        return "norms"
    if parts[0] in ("embed", "head"):
        return parts[0]
    if parts[0] == "mtp":
        return "mtp.proj"
    return f"{parts[0]}.{parts[1]}"


def group_squares(grads) -> Dict[str, Any]:
    """Σ g² per parameter group of a gradient tree."""
    import jax
    import jax.numpy as jnp

    out: Dict[str, Any] = {}

    def add(group, value):
        out[group] = out.get(group, 0.0) + value

    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = _path_name(path)
        group = group_of(name)
        g = jnp.square(g.astype(jnp.float32))
        if name.startswith("moe.") and group != "norms":
            add(group, jnp.sum(g[:-1]))
            add("mtp." + group[4:], jnp.sum(g[-1]))
        else:
            add(group, jnp.sum(g))
    return out


# -- the block ----------------------------------------------------------------


def _attention(q, k, v, seg, c: GlmConfig):
    """Causal, segment-masked attention of ONE sequence: [S, H, D]
    each → [S, H, Dv] in v's dtype. Tiles of at most ``attn_block``
    query rows, and only those between a block's earliest segment and
    the diagonal (:mod:`predictionio_tpu.ops.seq_attention`)."""
    return seq_backbone.attention(q, k, v, seg, c, c.softmax_scale)


def _mla(w, x, seg, pos, c: GlmConfig):
    """x [B, S, d] (normed) → [B, S, d], one sequence at a time: the
    heads' q, k, v of one sequence are live, not the batch's."""
    import jax
    import jax.numpy as jnp

    H, dn, dr, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                     c.qk_rope_head_dim, c.v_head_dim)
    eps = c.rms_norm_eps

    def one(args):
        x, seg, pos = args
        S = x.shape[0]
        cq = _rms(_mm(x, w["wqa"], c), w["q_norm"], eps)
        q = _mm(cq, w["wqb"], c).reshape(S, H, dn + dr)
        ckv = _mm(x, w["wkva"], c)
        k_r = _rope(ckv[:, c.kv_lora_rank:], pos, c.rope_theta, c.rope_freqs)
        kv = _mm(_rms(ckv[:, :c.kv_lora_rank], w["kv_norm"], eps),
                 w["wkvb"], c).reshape(S, H, dn + dv)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos[:, None], c.rope_theta,
                                c.rope_freqs)], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (S, H, dr))],
            -1)
        with scope("seqrec.mla.attention"):
            out = _attention(q.astype(_dt(c)), k.astype(_dt(c)),
                             kv[..., dn:].astype(_dt(c)), seg, c)
        # W_o takes the heads' outputs as attention leaves them: ONE
        # array for both backward passes to keep, not it and a reshape
        return jnp.tensordot(out, w["wo"].astype(_dt(c)).reshape(H, dv, -1),
                             2, preferred_element_type=jnp.float32)

    return jax.lax.map(one, (x, seg, pos))


def _block(w, x, seg, pos, bias, c: GlmConfig):
    """One layer on the residual stream x [B, S, d] float32; ``bias``
    None marks a dense layer."""
    B, S, d = x.shape
    with scope("seqrec.mla"):
        x = x + _mla(w["attn"], _rms(x, w["attn_norm"], c.rms_norm_eps),
                     seg, pos, c)
    with scope("seqrec.norm"):
        h = _rms(x, w["ffn_norm"], c.rms_norm_eps)
    if bias is None:
        with scope("seqrec.ffn"):
            return x + _swiglu(w["ffn"], h, c), None
    y, stats = _moe(w, h.reshape(B * S, d), seg.reshape(-1) > 0, bias, c)
    with scope("seqrec.residual"):
        return x + y.reshape(B, S, d), stats


def _mtp_entry(w, nxt, x, c: GlmConfig):
    """h' = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L)]: what the MTP
    module's block is given for a stream (``w``: ``params["mtp"]``)."""
    import jax.numpy as jnp

    with scope("seqrec.mtp"):
        return _mm(jnp.concatenate(
            [_rms(nxt, w["enorm"], c.rms_norm_eps),
             _rms(x, w["hnorm"], c.rms_norm_eps)], -1), w["eh_proj"], c)


def _stack(params, bias, batch, c: GlmConfig, mtp: bool = True):
    """Embedding, the main stack and (``mtp``) the MTP module: x_L and
    the module's output [B, S, d], and the expert layers' routing
    records (leading axis: layer, the module's block last).

    The module's block is the expert layers' LAST turn of one scanned
    body: when the main stack has ended, the turn first swaps the
    stream for h' = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L)] and
    keeps x_L beside it."""
    import jax
    import jax.numpy as jnp

    tokens, seg, pos = batch["tokens"], batch["seg"], batch["pos"]
    with scope("seqrec.embed"):
        x = params["embed"][tokens]
        nxt = params["embed"][batch["tgt1"]] if mtp else None
    n = c.n_moe_layers

    def dense(x, w):
        return jax.checkpoint(
            lambda w, x: _block(w, x, seg, pos, None, c)[0])(w, x), None

    def enter_mtp(x):
        return _mtp_entry(params["mtp"], nxt, x, c), x

    def turn(i, w, b, x, x_last):
        w = _cast_in_loop(w, c, i)
        if mtp:
            x, x_last = jax.lax.cond(i == n, enter_mtp,
                                     lambda x: (x, x_last), x)
        x, stats = _block(w, x, seg, pos, b, c)
        return (x, x_last), stats

    def sparse(carry, iwb):
        return jax.checkpoint(turn)(*iwb, *carry)

    turns = n + 1 if mtp else n
    with scope("seqrec.stack"):
        x, _ = jax.lax.scan(dense, x, params["dense"])
        (x, x_last), stats = jax.lax.scan(
            sparse, (x, x),
            (jnp.arange(turns),
             jax.tree.map(lambda a: a[:turns], params["moe"]),
             bias[:turns]))
    return (x_last, x, stats) if mtp else (x, None, stats)


def _head_logits(params, norm, x, c: GlmConfig):
    return _mm(_rms(x, norm, c.rms_norm_eps), params["head"], c)


def loss_fn(params, bias, batch, c: GlmConfig):
    """CE(next item) + λ·CE_MTP(item after next) and the step's
    records; ``batch``: tokens, seg, pos, tgt1, tgt2 [B, S] int32."""
    import jax.numpy as jnp

    x, xm, stats = _stack(params, bias, batch, c)
    n1 = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
    n2 = jnp.maximum((batch["tgt2"] > 0).sum(), 1)
    def head(norm):
        return lambda x: _head_logits(params, norm, x, c)

    ce1 = _chunked_ce(head(params["final_norm"]), x, batch["tgt1"], c) / n1
    ce2 = _chunked_ce(head(params["mtp"]["final_norm"]), xm, batch["tgt2"],
                      c) / n2
    return ce1 + c.mtp_loss_weight * ce2, {
        "loss": ce1, "mtp_loss": ce2, "moe": stats}


def logits_both(params, bias, batch, c: GlmConfig):
    """Both heads' float32 logits [B, S, V] for whole sequences (what
    the comparison with the reference reads)."""
    x, xm, _ = _stack(params, bias, batch, c)
    return (_head_logits(params, params["final_norm"], x, c),
            _head_logits(params, params["mtp"]["final_norm"], xm, c))


def _next_logits(params, bias, batch, n, c: GlmConfig):
    x, _, _ = _stack(params, bias, batch, c, mtp=False)
    return _head_logits(params, params["final_norm"], x[0, n - 1], c)


# -- the declaration ----------------------------------------------------------


BATCH_KEYS = ("tokens", "seg", "pos", "tgt1", "tgt2")

BACKBONE = seq_backbone.build(
    GlmConfig, param_shapes=param_shapes,
    # the expert layers, then the MTP module's block
    bias_shape=lambda c: (c.n_moe_layers + 1, c.router_experts),
    group_squares=group_squares, loss_fn=loss_fn,
    # a lambda, as the program it makes always was: ``jit__lambda`` is
    # its name in the persistent compile cache (seq_backbone.build)
    logits=lambda params, bias, batch, c: logits_both(params, bias, batch, c),
    next_logits=_next_logits, heads=("loss", "mtp_loss"),
    batch_keys=BATCH_KEYS)

# Built functions under the names this module had, for the benchmark's
# GLM-only files (not this repo's to edit; ROADMAP D9 points them at the
# table, and these four go then). Nothing else may read them: tests and
# the template go through ``BACKBONE``.
n_params = BACKBONE.n_params        # benchmark/generators/seq_train_jobs.py
init_state = BACKBONE.init_state    # benchmark/generators/seq_train_jobs.py
sequence_logits = BACKBONE.sequence_logits  # the same, seq_precision_probe.py
glm_train = BACKBONE.train          # benchmark/seq_precision_probe.py
