"""An ``sdar_moe`` block stack, trained by BLOCK DIFFUSION, as the
``sequentialrec`` backbone.

The item catalog takes the place of the token vocabulary: id 0 is PAD,
the vocabulary's LAST row is MASK, the items lie between. The block is
the published one (SDAR-30B-A3B-Chat, ``config.json``, ``model_type
sdar_moe``: the ``qwen3_moe`` block); h is the residual stream,
float32, and every layer is the same (``decoder_sparse_step`` 1, no
``mlp_only_layers``: no dense layer, no shared expert):

- **Attention**: a = RMSNorm(h); q = a W_q → H × D, k = a W_k →
  Hkv × D, v = a W_v → Hkv × D, no bias; RMSNorm over the D of each
  head on q and on k (gains of D each), THEN RoPE (rotate halves, all D
  dims, positions counted from the start of each segment); scores
  q·k/√D; query head h reads key-value head h ÷ (H ÷ Hkv);
  h′ = h + o W_o.
- **Experts**: m = RMSNorm(h′); logits = m W_r in float32 over the
  router's ``num_experts × ep_size`` experts; ids = top-k(logits);
  gates = softmax(logits)[ids] / Σ_ids softmax(logits) — which is the
  softmax over the SELECTED logits (``norm_topk_prob`` true); h″ = h′ +
  Σ_{e ∈ ids} gate_e · W_d^e(silu(W_g^e m) ⊙ W_u^e m). No bias, no
  scaling, no auxiliary loss. THIS chip holds experts ``ep_rank·n …
  (ep_rank+1)·n − 1`` and adds only their part
  (:mod:`predictionio_tpu.ops.moe_dispatch`).
- **Head**: RMSNorm_final(h_L) W_head, untied from the embedding.

**The objective and the mask** (block diffusion, arXiv:2503.09573,
which the model adopts). A segment is cut into blocks of
``block_length`` rows from its first row. Per step, every block draws
p = ε + (1 − ε)·u, u ~ U(0, 1), and each of its items becomes MASK
with probability p (:func:`seq_backbone.block_noise`). A step sends
every sequence through the stack TWICE in one pass — the history x⁰
(clean stream, rows ``0 … S − 1``) and the history with those items
replaced (noised stream, rows ``S … 2S − 1``), at the SAME positions —
under the block rule (:func:`seq_backbone.block_attention`): a clean
row sees the clean keys of its segment up to the end of its block; a
noised row the clean keys of the blocks before its own and the noised
keys of its own block; no clean row sees a noised key. The loss is

    Σ_{i masked} (1 / p_block(i)) · CE(logits of the NOISED row i, x⁰_i)
                                            ÷ the step's real events

— no shift: a MASK row predicts its OWN item, from the clean past and
the unmasked items of its block. Serving reads the next item at a MASK
row appended to the history (:func:`next_item_scores`); filling a
whole block over several denoising steps is not here.

The four equal layers are ONE scanned body, run on both streams at
once (the clean stream's keys and values feed the noised stream's
attention in the same layer). A layer turn of that scan is one
``jax.checkpoint``: the backward pass recomputes the turn — norms,
projections, RoPE, the expert half — from the residual stream that
entered it, EXCEPT the attention kernel's forward: the turn's policy
keeps its output and the rows' log-sum-exp (``seq_attention.KEPT``;
``attn_kept_bytes`` on the ``seqrec.fit`` span), so a step runs that
kernel once a layer, not twice. Precision, packing, the pieces any
backbone has, the train step and the verb's spans are
:mod:`predictionio_tpu.models.seq_backbone`'s. The train step's router
bias is carried as zeros and never moves (``bias_update_rate`` 0): this
router has none. The file ends in the backbone's declaration
(:func:`seq_backbone.build` makes the rest of it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Dict

import numpy as np

from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models.seq_backbone import (
    _cast_in_loop, _chunked_ce, _dt, _experts, _mm, _rms, _rope, _route,
    _stacked, _swiglu_shapes, scope)
from predictionio_tpu.ops import seq_attention


@dataclass(frozen=True)
class SdarConfig(seq_backbone.ArchitectureConfig):
    model_type: ClassVar[str] = "sdar_moe"
    #: what the published config may say and this file can honour
    _REQUIRED: ClassVar[Dict[str, Any]] = {
        "model_type": "sdar_moe", "attention_bias": False,
        "decoder_sparse_step": 1, "hidden_act": "silu",
        "mlp_only_layers": [], "norm_topk_prob": True, "rope_scaling": None,
        "tie_word_embeddings": False, "use_sliding_window": False}
    #: published keys that size nothing here: the dense width no layer
    #: has, a limit, and the window no layer uses
    _UNUSED: ClassVar[tuple] = ("intermediate_size",
                                "max_position_embeddings",
                                "max_window_layers", "sliding_window")
    _HELD: ClassVar[str] = "num_experts"
    #: this router has no bias: the step's rule moves it by nothing
    bias_update_rate: ClassVar[float] = 0.0
    hidden_size: int = 2048
    head_dim: int = 128
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    moe_intermediate_size: int = 768
    #: routed experts HELD here; the router is ``ep_size`` times as wide
    num_experts: int = 128
    ep_size: int = 1
    ep_rank: int = 0
    num_experts_per_tok: int = 8
    num_hidden_layers: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    vocab_size: int = 151936
    # -- the training job (not in the published config) ----------------
    #: rows of a block, and the least masking rate a block draws
    block_length: int = 4
    noise_eps: float = 1e-3
    seq_len: int = 8192
    seqs_per_step: int = 1
    clip_norm: float = 1.0
    init_std: float = 0.02
    matmul_dtype: str = "bfloat16"
    #: most query rows an attention tile holds; tokens per chunk of the
    #: loss: what bounds the program's temporaries
    attn_block: int = 512
    token_chunk: int = 4096

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]) -> "SdarConfig":
        c = super().from_architecture(arch)
        if c.num_attention_heads % c.num_key_value_heads:
            raise ValueError(f"{c.num_attention_heads} query heads over "
                             f"{c.num_key_value_heads} key-value heads")
        if c.num_experts_per_tok > c.router_experts:
            raise ValueError(f"top-{c.num_experts_per_tok} of a router of "
                             f"{c.router_experts}")
        if c.block_length < 1 or c.seq_len % c.block_length:
            raise ValueError(f"blocks of {c.block_length} rows do not "
                             f"divide a sequence of {c.seq_len}")
        if not 0.0 < c.noise_eps < 1.0:
            raise ValueError(f"noise_eps {c.noise_eps} outside (0, 1)")
        return c

    @property
    def mask_id(self) -> int:
        """MASK: the vocabulary's last row (items are 1 … this − 1)."""
        return self.vocab_size - 1


# -- parameters ---------------------------------------------------------------


def _layer_shapes(c: SdarConfig) -> Dict[str, Any]:
    d, D = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * D, c.num_key_value_heads * D
    return {"attn_norm": (d,), "ffn_norm": (d,),
            "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                     "q_norm": (D,), "k_norm": (D,), "wo": (q, d)},
            "router": (d, c.router_experts),
            "experts": _swiglu_shapes(d, c.moe_intermediate_size,
                                      (c.num_experts,))}


def param_shapes(c: SdarConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``layers`` carries a leading layer
    axis: the identical layers are ONE scanned body."""
    return {"embed": (c.vocab_size, c.hidden_size),
            "layers": _stacked(_layer_shapes(c), c.num_hidden_layers),
            "final_norm": (c.hidden_size,),
            "head": (c.hidden_size, c.vocab_size)}


def group_of(name: str) -> str:
    """The parameter group a leaf's gradient norm is recorded under:
    by part, over all the layers."""
    parts = name.split(".")
    if parts[-1].endswith("norm"):
        return "norms"
    return parts[0] if parts[0] in ("embed", "head") else parts[1]


def group_squares(grads) -> Dict[str, Any]:
    """Σ g² per parameter group of a gradient tree."""
    return seq_backbone.squares_by_group(grads, group_of)


# -- the block ----------------------------------------------------------------


def _attend(w, x, seg, pos, c: SdarConfig):
    """x [B, R, d] (normed) → [B, R, d], one sequence at a time: R = 2·S
    rows, the clean stream and then the noised one at the same
    positions — or R = S, one stream under the clean stream's rule."""
    import jax
    import jax.numpy as jnp

    H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    eps, scale = c.rms_norm_eps, 1.0 / np.sqrt(D)

    def one(args):
        x, seg, pos = args
        R = x.shape[0]
        at = jnp.tile(pos, R // pos.shape[0])[:, None]
        q = _rope(_rms(_mm(x, w["wq"], c).reshape(R, H, D), w["q_norm"],
                       eps), at, c.rope_theta)
        k = _rope(_rms(_mm(x, w["wk"], c).reshape(R, Hkv, D), w["k_norm"],
                       eps), at, c.rope_theta)
        v = _mm(x, w["wv"], c).reshape(R, Hkv, D)
        with scope("seqrec.bd.attention"):
            out = seq_backbone.block_attention(
                q.astype(_dt(c)), k.astype(_dt(c)), v.astype(_dt(c)), seg,
                c, scale, c.block_length)
        return jnp.tensordot(out, w["wo"].astype(_dt(c)).reshape(H, D, -1),
                             2, preferred_element_type=jnp.float32)

    return jax.lax.map(one, (x, seg, pos))


def _layer(w, x, seg, pos, c: SdarConfig):
    """One layer on the residual stream x [B, R, d] float32 of the
    sequences' streams (R = 2·S or S rows of ``seg``'s S slots)."""
    import jax.numpy as jnp

    B, R, d = x.shape
    with scope("seqrec.bd"):
        x = x + _attend(w["attn"], _rms(x, w["attn_norm"], c.rms_norm_eps),
                        seg, pos, c)
    with scope("seqrec.norm"):
        m = _rms(x, w["ffn_norm"], c.rms_norm_eps).reshape(B * R, d)
    valid = jnp.tile(seg > 0, (1, R // seg.shape[1])).reshape(-1)
    gates, plan, stats = _route(w["router"], m, valid, None, c, softmax=True)
    y = _experts(w, m, gates, plan, c)
    with scope("seqrec.residual"):
        return x + y.reshape(B, R, d), stats


def _stack(params, bias, batch, c: SdarConfig):
    """Embedding and the stack: h_L [B, R, d] and the layers' routing
    records (leading axis: layer). With ``batch["noised"]`` (the
    noised stream's tokens) R = 2·S, the clean rows first; without,
    ONE stream of ``batch["tokens"]`` under the clean stream's rule.
    ``bias`` is the step's zero router bias: nothing reads it. A layer
    turn is one ``jax.checkpoint`` that keeps what attention names
    (:data:`seq_attention.KEPT`) and recomputes the rest."""
    import jax
    import jax.numpy as jnp

    del bias
    seg, pos, tokens = batch["seg"], batch["pos"], batch["tokens"]
    kept = jax.checkpoint_policies.save_only_these_names(*seq_attention.KEPT)
    if "noised" in batch:
        tokens = jnp.concatenate([tokens, batch["noised"]], axis=1)
    with scope("seqrec.embed"):
        x = params["embed"][tokens]

    def turn(x, iw):
        i, w = iw
        return _layer(_cast_in_loop(w, c, i), x, seg, pos, c)

    with scope("seqrec.stack"):
        return jax.lax.scan(
            lambda x, iw: jax.checkpoint(turn, policy=kept)(x, iw), x,
            (jnp.arange(c.num_hidden_layers), params["layers"]))


def _head_logits(params, x, c: SdarConfig):
    """The untied head: the final norm, then W_head."""
    return _mm(_rms(x, params["final_norm"], c.rms_norm_eps),
               params["head"], c)


def draw_noise(draw, step, seg, c: SdarConfig):
    """A step's masks for the sequences of a batch: ``draw`` [B, 2]
    uint32 — the train's seed and each sequence's number in the packed
    order —, ``step`` the steps taken before → (``masked`` [B, S] bool,
    ``weight`` [B, S] float32), by :func:`seq_backbone.block_noise`."""
    import jax

    return jax.vmap(lambda d, seg: seq_backbone.block_noise(
        d[0], step, d[1], seg, c.block_length, c.noise_eps))(draw, seg)


def loss_fn(params, bias, batch, c: SdarConfig):
    """Σ_masked CE(the noised row's logits, its own item) / p of its
    block, over the step's real events, and the step's records;
    ``batch``: tokens, seg, pos [B, S] int32, ``draw`` [B, 2] uint32
    and ``step`` (:func:`draw_noise`)."""
    import jax.numpy as jnp

    tokens, seg = batch["tokens"], batch["seg"]
    with scope("seqrec.bd.noise"):
        masked, weight = draw_noise(batch["draw"], batch["step"], seg, c)
        noised = jnp.where(masked, c.mask_id, tokens)
    x, stats = _stack(params, bias, dict(batch, noised=noised), c)
    real = (seg > 0).sum()
    ce = _chunked_ce(lambda x: _head_logits(params, x, c),
                     x[:, tokens.shape[1]:], tokens, c,
                     weights=weight) / jnp.maximum(real, 1)
    return ce, {"loss": ce, "moe": stats, "bd_masked": masked.sum(),
                "bd_real": real}


def draws(n_sequences: int, seed: int) -> np.ndarray:
    """[sequences, 2] uint32: what keys each packed sequence's noise
    (the seed, its number)."""
    return np.stack([np.full(n_sequences, seed % (1 << 32), np.uint32),
                     np.arange(n_sequences, dtype=np.uint32)], axis=1)


def first_noise(packed, c: SdarConfig, seed: int, step: int = 0):
    """The streams a train of ``seed`` sees at ``step`` for every
    sequence of ``packed`` as if it were in that step's batch (``noised``
    [N, S] int32, ``weight`` [N, S] float32), made by the program's own
    noise function — for the comparison with the plain reference, whose
    noise is its input (``benchmark/generators/sdar_train_jobs.py``)."""
    import jax
    import jax.numpy as jnp

    masked, weight = jax.jit(
        lambda draw, seg: draw_noise(draw, jnp.int32(step), seg, c))(
            draws(packed.tokens.shape[0], seed), packed.seg)
    return (np.where(np.asarray(masked), c.mask_id,
                     packed.tokens).astype(np.int32), np.asarray(weight))


def logits(params, bias, batch, c: SdarConfig):
    """The head's logits at the NOISED stream's rows of whole packed
    sequences (``batch["noised"]``: that stream's tokens, given)."""
    S = batch["tokens"].shape[1]
    x, _ = _stack(params, bias, batch, c)
    return (_head_logits(params, x[:, S:], c),)


def _next_logits(params, bias, batch, n, c: SdarConfig):
    """The history with MASK rows appended to the end of the block
    after it (:func:`seq_backbone.next_item_scores`), one segment and
    ONE stream under the clean stream's rule (a row sees the keys up to
    the end of its block): the logits at the first MASK row."""
    import jax.numpy as jnp

    del n
    x, _ = _stack(params, bias, batch, c)
    at = jnp.argmax(batch["tokens"][0] == c.mask_id)
    return _head_logits(params, x[0, at], c)


def attn_kept_bytes(c: SdarConfig) -> int:
    """Bytes a step keeps for the backward pass on account of the
    turn's policy: per layer and sequence the kernel's output over both
    streams' rows in the products' dtype and a float32 a row and head."""
    rows = c.num_hidden_layers * c.seqs_per_step * 2 * c.seq_len
    return rows * c.num_attention_heads * (
        c.head_dim * _dt(c).itemsize + 4)


def _refuse_mask_items(packed, c: SdarConfig) -> Dict[str, Any]:
    top = int(packed.tokens.max())
    if top >= c.mask_id:
        raise ValueError(f"item id {top} is the MASK row {c.mask_id} "
                         "of the vocabulary or beyond it")
    return {}


# -- the declaration ----------------------------------------------------------

#: what ``sequence_logits`` reads of a batch: both streams' tokens (the
#: noise is DATA there); ``weight`` is the loss's, for the comparison
BATCH_KEYS = ("tokens", "seg", "pos", "noised", "weight")
#: what a train's batches hold beside ``draw``
TRAIN_KEYS = ("tokens", "seg", "pos")

BACKBONE = seq_backbone.build(
    SdarConfig, param_shapes=param_shapes,
    bias_shape=lambda c: (c.num_hidden_layers, c.router_experts),
    group_squares=group_squares, loss_fn=loss_fn,
    counted=True,       # the loss folds the steps taken into its noise
    logits=logits, next_logits=_next_logits, heads=("loss",),
    batch_keys=BATCH_KEYS, train_keys=TRAIN_KEYS,
    pack_attrs=_refuse_mask_items,
    draws=lambda packed, seed: {
        "draw": draws(packed.tokens.shape[0], seed)},
    fit_attrs=lambda c: {"attn_kept_bytes": attn_kept_bytes(c)})

n_params = BACKBONE.n_params    # benchmark/tests/test_sdar_layers.py
