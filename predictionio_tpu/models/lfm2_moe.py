"""An ``lfm2_moe`` hybrid block stack as the ``sequentialrec`` backbone.

The item catalog takes the place of the token vocabulary. Every
equation is the published block's (LFM2-8B-A1B, ``config.json``,
``model_type lfm2_moe``):

- **Layer**: x ← x + Op(RMSNorm_op(x)); x ← x + FFN(RMSNorm_ffn(x)).
  Op is the gated short convolution where ``layer_types[l]`` is
  ``"conv"``, grouped-query attention where ``"full_attention"``. FFN
  is a dense SwiGLU (``intermediate_size``) in the first
  ``num_dense_layers`` layers, the sparse-expert layer after.
- **Gated short convolution**: [B ; C ; u] = x W_in (d → 3d, no bias,
  chunks in this order); z = B ⊙ u; c_t = Σ_{j=0..L−1} w[j] ⊙
  z_{t−(L−1)+j} (depthwise, causal, L = ``conv_L_cache`` = 3 taps,
  zeros before the first position); y = (C ⊙ c) W_out. No activation,
  no bias. On PACKED histories a tap that reaches before its segment's
  first row is zero too — what the unpacked model's left padding does.
- **Attention**: q = x W_q → H × D, k = x W_k → Hkv × D, v = x W_v →
  Hkv × D; RMSNorm over the D of each head on q and on k (gains of D
  each), THEN RoPE (rotate halves, all D dims, positions counted from
  the start of each segment); scores q·k/√D, causal AND inside one
  segment; query head h reads key-value head h ÷ (H ÷ Hkv); output
  W_o. No bias.
- **Router**: s = sigmoid(x W_r) in float32 over the router's
  ``num_experts × ep_size`` experts; top-k of s + b, b
  (``expert_bias``) a selection-only bias without gradient; gates
  scaling · s_e/Σ_selected s. Nothing else is added: there is no
  shared expert. THIS chip holds experts ``ep_rank·n … (ep_rank+1)·n
  − 1`` and adds only their part
  (:mod:`predictionio_tpu.ops.moe_dispatch`). After each step b moves
  against the load: b_e ← b_e + γ·sign(mean load − load_e).
- **Head**: RMSNorm_final(x_L) Eᵀ, E the embedding (tied). Loss: the
  cross-entropy of the next item, a mean over the real targets.

The stack's layers are of different kinds and shapes. A run of
consecutive layers of one kind (operator × FFN) is ONE scanned body
over its stacked weights (``params["runs"][r]``), the runs in
``layer_types`` order: the published 24 layers are seven kinds of run,
the benchmark's five layers three (conv + dense, attention + experts,
3 × conv + experts).

Precision, packing, the pieces any backbone has, the train step and
the verb's spans are :mod:`predictionio_tpu.models.seq_backbone`'s.
Here besides: the convolution's taps and both gate products are
float32. The file ends in the backbone's declaration
(:func:`seq_backbone.build` makes the rest of it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Dict, List, Tuple

import numpy as np

from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models.seq_backbone import (
    _cast_in_loop, _chunked_ce, _dt, _mm, _moe, _path_name, _rms, _rope,
    _stacked, _swiglu, _swiglu_shapes, scope)

_OPS = ("conv", "full_attention")


@dataclass(frozen=True)
class Lfm2Config(seq_backbone.ArchitectureConfig):
    model_type: ClassVar[str] = "lfm2_moe"
    #: what the published config may say and this file can honour
    _REQUIRED: ClassVar[Dict[str, Any]] = {
        "model_type": "lfm2_moe", "conv_bias": False,
        "use_expert_bias": True, "tie_word_embeddings": True}
    #: published keys that size nothing here (a limit, not a shape)
    _UNUSED: ClassVar[tuple] = ("max_position_embeddings",)
    _HELD: ClassVar[str] = "num_experts"
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention",
                                    "conv")
    num_hidden_layers: int = 4
    num_dense_layers: int = 2
    #: routed experts HELD here; the router is ``ep_size`` times as wide
    num_experts: int = 32
    ep_size: int = 1
    ep_rank: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    vocab_size: int = 65536
    # -- the training job (not in the published config) ----------------
    seq_len: int = 4096
    seqs_per_step: int = 8
    bias_update_rate: float = 1e-3
    clip_norm: float = 1.0
    init_std: float = 0.02
    matmul_dtype: str = "bfloat16"
    #: most query rows an attention tile holds; tokens per chunk of a
    #: SwiGLU and of the loss: what bounds the program's temporaries
    attn_block: int = 512
    token_chunk: int = 4096

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]) -> "Lfm2Config":
        c = super().from_architecture(arch)
        if len(c.layer_types) != c.num_hidden_layers:
            raise ValueError(f"{len(c.layer_types)} layer_types for "
                             f"{c.num_hidden_layers} layers")
        other = sorted(set(c.layer_types) - set(_OPS))
        if other:
            raise ValueError(f"layer_types {other}: implemented are "
                             f"{list(_OPS)}")
        if (c.hidden_size % c.num_attention_heads
                or c.num_attention_heads % c.num_key_value_heads):
            raise ValueError(
                f"{c.num_attention_heads} query heads over "
                f"{c.num_key_value_heads} key-value heads of a hidden "
                f"size of {c.hidden_size}")
        if not 0 <= c.num_dense_layers < c.num_hidden_layers:
            raise ValueError(f"num_dense_layers {c.num_dense_layers} of "
                             f"{c.num_hidden_layers} layers leaves no "
                             "expert layer")
        return c

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def runs(self) -> Tuple[Tuple[str, bool, int], ...]:
        """(operator, dense FFN?, layers) of each run of consecutive
        layers of one kind, in ``layer_types`` order."""
        out: List[List] = []
        for l, op in enumerate(self.layer_types):
            kind = (op, l < self.num_dense_layers)
            if out and tuple(out[-1][:2]) == kind:
                out[-1][2] += 1
            else:
                out.append([*kind, 1])
        return tuple(tuple(r) for r in out)


# -- parameters ---------------------------------------------------------------


def _layer_shapes(c: Lfm2Config, op: str, dense: bool) -> Dict[str, Any]:
    d, D = c.hidden_size, c.head_dim
    out: Dict[str, Any] = {"op_norm": (d,), "ffn_norm": (d,)}
    if op == "conv":
        out["conv"] = {"w_in": (d, 3 * d), "taps": (c.conv_L_cache, d),
                       "w_out": (d, d)}
    else:
        kv = c.num_key_value_heads * D
        out["attn"] = {"wq": (d, d), "wk": (d, kv), "wv": (d, kv),
                       "q_norm": (D,), "k_norm": (D,), "wo": (d, d)}
    if dense:
        out["ffn"] = _swiglu_shapes(d, c.intermediate_size)
    else:
        out["router"] = (d, c.router_experts)
        out["experts"] = _swiglu_shapes(d, c.moe_intermediate_size,
                                        (c.num_experts,))
    return out


def param_shapes(c: Lfm2Config) -> Dict[str, Any]:
    """The parameter tree as shapes. ``runs[r]`` carries a leading
    layer axis: a run's identical layers are ONE scanned body. No
    ``head``: the embedding is the head."""
    return {"embed": (c.vocab_size, c.hidden_size),
            "runs": [_stacked(_layer_shapes(c, op, dense), n)
                     for op, dense, n in c.runs],
            "final_norm": (c.hidden_size,)}


def group_of(name: str) -> str:
    """The parameter group a leaf's gradient norm is recorded under:
    by part, over all the layers that have it."""
    parts = name.split(".")
    if parts[-1].endswith("norm"):
        return "norms"
    return "embed" if parts[0] == "embed" else parts[2]


def group_squares(grads) -> Dict[str, Any]:
    """Σ g² per parameter group of a gradient tree."""
    return seq_backbone.squares_by_group(grads, group_of)


# -- the block ----------------------------------------------------------------


def conv_masked_taps(pos: np.ndarray, seg: np.ndarray, taps: int) -> int:
    """Taps that reach before their segment's first row (and are
    zeroed), over the real rows of packed sequences, for ONE conv
    layer: a row at position p loses max(taps − 1 − p, 0)."""
    return int((np.maximum(taps - 1 - pos, 0) * (seg > 0)).sum())


def _conv_mix(b, cc, u, taps, pos):
    """(C ⊙ conv(B ⊙ u)) in float32: b, cc, u [B, S, d]; ``taps``
    [L, d], tap j reads the row L − 1 − j before; ``pos`` [B, S], a
    row's position inside its segment — a tap that reaches further
    back than that is zero."""
    import jax.numpy as jnp

    S, L = b.shape[1], taps.shape[0]
    z = b * u
    c = z * taps[L - 1]
    for back in range(1, L):
        before = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :S]
        c = c + jnp.where((pos >= back)[..., None], before,
                          0.0) * taps[L - 1 - back]
    return cc * c


def _shortconv(w, x, pos, c: Lfm2Config):
    """x [B, S, d] (normed) → [B, S, d]."""
    import jax.numpy as jnp

    b, cc, u = jnp.split(_mm(x, w["w_in"], c), 3, axis=-1)
    with scope("seqrec.conv.mix"):
        y = _conv_mix(b, cc, u, w["taps"].astype(jnp.float32), pos)
    return _mm(y, w["w_out"], c)


def _gqa(w, x, seg, pos, c: Lfm2Config):
    """x [B, S, d] (normed) → [B, S, d], one sequence at a time."""
    import jax
    import jax.numpy as jnp

    H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    eps, scale = c.norm_eps, 1.0 / np.sqrt(D)

    def one(args):
        x, seg, pos = args
        S = x.shape[0]
        q = _rope(_rms(_mm(x, w["wq"], c).reshape(S, H, D), w["q_norm"],
                       eps), pos[:, None], c.rope_theta)
        k = _rope(_rms(_mm(x, w["wk"], c).reshape(S, Hkv, D), w["k_norm"],
                       eps), pos[:, None], c.rope_theta)
        v = _mm(x, w["wv"], c).reshape(S, Hkv, D)
        with scope("seqrec.gqa.attention"):
            out = seq_backbone.attention(
                q.astype(_dt(c)), k.astype(_dt(c)), v.astype(_dt(c)), seg,
                c, scale)
        return jnp.tensordot(out, w["wo"].astype(_dt(c)).reshape(H, D, -1),
                             2, preferred_element_type=jnp.float32)

    return jax.lax.map(one, (x, seg, pos))


def _layer(w, x, seg, pos, bias, c: Lfm2Config, op: str):
    """One layer on the residual stream x [B, S, d] float32; ``bias``
    None marks a dense layer."""
    B, S, d = x.shape
    if op == "conv":
        with scope("seqrec.conv"):
            x = x + _shortconv(w["conv"], _rms(x, w["op_norm"], c.norm_eps),
                               pos, c)
    else:
        with scope("seqrec.gqa"):
            x = x + _gqa(w["attn"], _rms(x, w["op_norm"], c.norm_eps), seg,
                         pos, c)
    with scope("seqrec.norm"):
        h = _rms(x, w["ffn_norm"], c.norm_eps)
    if bias is None:
        with scope("seqrec.ffn"):
            return x + _swiglu(w["ffn"], h, c), None
    y, stats = _moe(w, h.reshape(B * S, d), seg.reshape(-1) > 0, bias, c)
    with scope("seqrec.residual"):
        return x + y.reshape(B, S, d), stats


def _stack(params, bias, batch, c: Lfm2Config):
    """Embedding and the stack: x_L [B, S, d] and the expert layers'
    routing records (leading axis: expert layer, in stack order)."""
    import jax
    import jax.numpy as jnp

    seg, pos = batch["seg"], batch["pos"]
    with scope("seqrec.embed"):
        x = params["embed"][batch["tokens"]]
    stats, at = [], 0
    for (op, dense, n), w in zip(c.runs, params["runs"]):
        def turn(x, iwb, op=op):
            i, w, *b = iwb
            w = _cast_in_loop(w, c, i, aside=("router", "taps"))
            return _layer(w, x, seg, pos, b[0] if b else None, c, op)

        xs = (jnp.arange(n), w) + (() if dense else (bias[at:at + n],))
        with scope("seqrec.stack"):
            x, s = jax.lax.scan(
                lambda x, iwb: jax.checkpoint(turn)(x, iwb), x, xs)
        if not dense:
            stats.append(s)
            at += n
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *stats)


def _head_logits(params, x, c: Lfm2Config):
    """The tied head: the final norm, then the embedding transposed."""
    return _mm(_rms(x, params["final_norm"], c.norm_eps),
               params["embed"].T, c)


def loss_fn(params, bias, batch, c: Lfm2Config):
    """CE(next item), a mean over the real targets, and the step's
    records; ``batch``: tokens, seg, pos, tgt1 [B, S] int32."""
    import jax.numpy as jnp

    x, stats = _stack(params, bias, batch, c)
    n = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
    ce = _chunked_ce(lambda x: _head_logits(params, x, c), x,
                     batch["tgt1"], c) / n
    return ce, {"loss": ce, "moe": stats}


def _next_logits(params, bias, batch, n, c: Lfm2Config):
    x, _ = _stack(params, bias, batch, c)
    return _head_logits(params, x[0, n - 1], c)


# -- the declaration ----------------------------------------------------------


def _conv_layers(c: Lfm2Config) -> int:
    return sum(op == "conv" for op in c.layer_types)


BATCH_KEYS = ("tokens", "seg", "pos", "tgt1")

BACKBONE = seq_backbone.build(
    Lfm2Config, param_shapes=param_shapes,
    bias_shape=lambda c: (c.n_moe_layers, c.router_experts),
    group_squares=group_squares, loss_fn=loss_fn,
    logits=lambda params, bias, batch, c: (_head_logits(
        params, _stack(params, bias, batch, c)[0], c),),
    next_logits=_next_logits, heads=("loss",), batch_keys=BATCH_KEYS,
    pack_attrs=lambda packed, c: {"conv_masked_taps": conv_masked_taps(
        packed.pos, packed.seg, c.conv_L_cache)},
    fit_attrs=lambda c: {
        "conv_layers": _conv_layers(c),
        "attn_layers": c.num_hidden_layers - _conv_layers(c)})

n_params = BACKBONE.n_params    # benchmark/tests/test_lfm2_layers.py
