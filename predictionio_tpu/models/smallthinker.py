"""A ``smallthinker`` block stack as the ``sequentialrec`` backbone.

The item catalog takes the place of the token vocabulary. Every
equation is the published block's (SmallThinker-21BA3B-Instruct,
``config.json``); h is the residual stream, float32:

- **Layer kinds**: layer l is ``global`` where
  ``sliding_window_layout[l]`` = ``rope_layout[l]`` = 0 (the published
  layers 0, 4, 8, …), else ``window``: one global layer, then three
  window layers, all 52 of them expert layers (no dense layer, no
  shared expert).
- **Router, BEFORE attention**: logits = h W_r over the router's
  ``moe_num_primary_experts × ep_size`` experts — the layer's INPUT,
  before any norm; ids = top-k(logits), gates = softmax(logits[ids])
  (they sum to 1, so ``norm_topk_prob`` adds nothing). No bias, no
  scaling, no auxiliary loss. THIS chip holds experts ``ep_rank·n …
  (ep_rank+1)·n − 1`` and adds only their part
  (:mod:`predictionio_tpu.ops.moe_dispatch`).
- **Attention**: a = RMSNorm(h); q = a W_q → H × D, k = a W_k →
  Hkv × D, v = a W_v → Hkv × D, no bias, no QK norm. A ``window``
  layer applies RoPE (rotate halves, all D dims, positions counted from
  the start of each segment) and a query sees its newest
  ``sliding_window_size`` keys; a ``global`` layer applies NO positional
  encoding and sees its whole segment. Scores q·k/√D, causal AND inside
  one segment; query head h reads key-value head h ÷ (H ÷ Hkv);
  h′ = h + o W_o.
- **Experts, AFTER attention, through the route made before it**:
  m = RMSNorm(h′); h″ = h′ + Σ_{e ∈ ids} gate_e · W_d^e(relu(W_g^e m) ⊙
  W_u^e m) — ReGLU.
- **Head**: RMSNorm_final(h_L) W_head, untied from the embedding. Loss:
  the cross-entropy of the next item, a mean over the real targets.

A run of consecutive layers of one kind is ONE scanned body over its
stacked weights (``params["runs"][r]``), so the global and the window
layers carry scopes of their own (``seqrec.gqa*`` / ``seqrec.swa*``):
the published 52 layers are 26 runs, the benchmark's period of four
two (1 × global, 3 × window). A layer turn of a scan is one
``jax.checkpoint``: the backward pass recomputes the turn from the
residual stream that entered it. A GLOBAL layer's turn keeps the
attention kernel's output and the rows' log-sum-exp
(``seq_attention.KEPT``; ``attn_kept_bytes`` on the ``seqrec.fit``
span) and so runs that kernel once a step, not twice; a WINDOW layer's
turn keeps nothing: what a layer keeps costs the same bytes whatever
its kind, and a window layer's forward is only the window's share of a
global one's.

Precision, packing, the pieces any backbone has, the train step and
the verb's spans are :mod:`predictionio_tpu.models.seq_backbone`'s.
The train step's router bias is carried as zeros and never moves
(``bias_update_rate`` 0): this router has none. The file ends in the backbone's declaration
(:func:`seq_backbone.build` makes the rest of it).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, ClassVar, Dict, List, Optional, Tuple

import numpy as np

from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models.seq_backbone import (
    _cast_in_loop, _chunked_ce, _dt, _experts, _mm, _rms, _rope, _route,
    _stacked, _swiglu_shapes, scope)
from predictionio_tpu.ops import seq_attention

KINDS = ("global", "window")


@dataclass(frozen=True)
class SmallThinkerConfig(seq_backbone.ArchitectureConfig):
    model_type: ClassVar[str] = "smallthinker"
    #: what the published config may say and this file can honour
    _REQUIRED: ClassVar[Dict[str, Any]] = {
        "model_type": "smallthinker",
        "moe_primary_router_apply_softmax": True,
        "tie_word_embeddings": False, "rope_scaling": None}
    #: published keys that size nothing here: a name, a limit, and a
    #: switch that changes nothing (the softmax over the selected sums
    #: to 1)
    _UNUSED: ClassVar[tuple] = ("model_name", "max_position_embeddings",
                                "norm_topk_prob")
    _HELD: ClassVar[str] = "moe_num_primary_experts"
    #: this router has no bias: the step's rule moves it by nothing
    bias_update_rate: ClassVar[float] = 0.0
    hidden_size: int = 2560
    head_dim: int = 128
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    moe_ffn_hidden_size: int = 768
    #: routed experts HELD here; the router is ``ep_size`` times as wide
    moe_num_primary_experts: int = 64
    ep_size: int = 1
    ep_rank: int = 0
    moe_num_active_primary_experts: int = 6
    num_hidden_layers: int = 4
    #: per layer, 1 = a window of ``sliding_window_size`` keys / rotary
    #: positions; the two layouts are one (a layer without a window has
    #: no positions)
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    sliding_window_size: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    vocab_size: int = 151936
    # -- the training job (not in the published config) ----------------
    seq_len: int = 16384
    seqs_per_step: int = 2
    clip_norm: float = 1.0
    init_std: float = 0.02
    matmul_dtype: str = "bfloat16"
    #: most query rows an attention tile holds; tokens per chunk of the
    #: loss: what bounds the program's temporaries
    attn_block: int = 512
    token_chunk: int = 4096

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]) -> "SmallThinkerConfig":
        layouts = {key: [int(v) for v in arch[key]]
                   for key in ("sliding_window_layout", "rope_layout")
                   if key in arch}
        c = super().from_architecture(dict(arch, **layouts))
        if len(c.sliding_window_layout) != c.num_hidden_layers:
            raise ValueError(f"{len(c.sliding_window_layout)} entries of "
                             f"sliding_window_layout for "
                             f"{c.num_hidden_layers} layers")
        if c.rope_layout != c.sliding_window_layout:
            raise ValueError("rope_layout differs from sliding_window_layout:"
                             " only window layers with rotary positions and "
                             "global layers without are implemented")
        if set(c.sliding_window_layout) - {0, 1}:
            raise ValueError("sliding_window_layout holds other than 0 and 1")
        if c.num_attention_heads % c.num_key_value_heads:
            raise ValueError(f"{c.num_attention_heads} query heads over "
                             f"{c.num_key_value_heads} key-value heads")
        if c.moe_num_active_primary_experts > c.router_experts:
            raise ValueError(f"top-{c.moe_num_active_primary_experts} of a "
                             f"router of {c.router_experts}")
        return c

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_num_active_primary_experts

    @property
    def window(self) -> Optional[int]:
        """The key window of the window layers; None where no layer
        has one."""
        return (self.sliding_window_size if any(self.sliding_window_layout)
                else None)

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """(kind, layers) of each run of consecutive layers of one
        kind, in stack order."""
        out: List[List] = []
        for flag in self.sliding_window_layout:
            if out and out[-1][0] == KINDS[flag]:
                out[-1][1] += 1
            else:
                out.append([KINDS[flag], 1])
        return tuple(tuple(r) for r in out)


# -- parameters ---------------------------------------------------------------


def _layer_shapes(c: SmallThinkerConfig) -> Dict[str, Any]:
    d = c.hidden_size
    q, kv = (c.num_attention_heads * c.head_dim,
             c.num_key_value_heads * c.head_dim)
    return {"attn_norm": (d,), "ffn_norm": (d,),
            "attn": {"wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                     "wo": (q, d)},
            "router": (d, c.router_experts),
            "experts": _swiglu_shapes(d, c.moe_ffn_hidden_size,
                                      (c.moe_num_primary_experts,))}


def param_shapes(c: SmallThinkerConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``runs[r]`` carries a leading
    layer axis: a run's identical layers are ONE scanned body."""
    return {"embed": (c.vocab_size, c.hidden_size),
            "runs": [_stacked(_layer_shapes(c), n) for _, n in c.runs],
            "final_norm": (c.hidden_size,),
            "head": (c.hidden_size, c.vocab_size)}


def group_of(name: str) -> str:
    """The parameter group a leaf's gradient norm is recorded under:
    by part, over all the layers that have it."""
    parts = name.split(".")
    if parts[-1].endswith("norm"):
        return "norms"
    return parts[0] if parts[0] in ("embed", "head") else parts[2]


def group_squares(grads) -> Dict[str, Any]:
    """Σ g² per parameter group of a gradient tree."""
    return seq_backbone.squares_by_group(grads, group_of)


# -- the block ----------------------------------------------------------------


def _attend(w, x, seg, pos, c: SmallThinkerConfig, kind: str):
    """x [B, S, d] (normed) → [B, S, d], one sequence at a time; a
    ``window`` layer rotates q and k and sees ``sliding_window_size``
    keys, a ``global`` layer does neither."""
    import jax
    import jax.numpy as jnp

    H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    windowed = kind == "window"
    name = "seqrec.swa.attention" if windowed else "seqrec.gqa.attention"

    def one(args):
        x, seg, pos = args
        S = x.shape[0]
        q = _mm(x, w["wq"], c).reshape(S, H, D)
        k = _mm(x, w["wk"], c).reshape(S, Hkv, D)
        v = _mm(x, w["wv"], c).reshape(S, Hkv, D)
        if windowed:
            q = _rope(q, pos[:, None], c.rope_theta)
            k = _rope(k, pos[:, None], c.rope_theta)
        with scope(name):
            out = seq_backbone.attention(
                q.astype(_dt(c)), k.astype(_dt(c)), v.astype(_dt(c)), seg,
                c, 1.0 / np.sqrt(D),
                c.sliding_window_size if windowed else None)
        return jnp.tensordot(out, w["wo"].astype(_dt(c)).reshape(H, D, -1),
                             2, preferred_element_type=jnp.float32)

    return jax.lax.map(one, (x, seg, pos))


def _layer(w, x, seg, pos, c: SmallThinkerConfig, kind: str):
    """One layer on the residual stream x [B, S, d] float32: the route
    from the layer's INPUT, attention, the experts through that route."""
    import jax

    B, S, d = x.shape
    gates, plan, stats = _route(w["router"], x.reshape(B * S, d),
                                seg.reshape(-1) > 0, None, c, softmax=True)
    with scope("seqrec.swa" if kind == "window" else "seqrec.gqa"):
        x = x + _attend(w["attn"], _rms(x, w["attn_norm"], c.rms_norm_eps),
                        seg, pos, c, kind)
    with scope("seqrec.norm"):
        m = _rms(x, w["ffn_norm"], c.rms_norm_eps)
    y = _experts(w, m.reshape(B * S, d), gates, plan, c, act=jax.nn.relu)
    with scope("seqrec.residual"):
        return x + y.reshape(B, S, d), stats


def _stack(params, bias, batch, c: SmallThinkerConfig):
    """Embedding and the stack: h_L [B, S, d] and the layers' routing
    records (leading axis: layer, in stack order). ``bias`` is the
    step's zero router bias: nothing reads it. A layer turn is one
    ``jax.checkpoint``; a global layer's keeps what attention names
    (:data:`seq_attention.KEPT`), a window layer's nothing: the bytes
    are the same, the kernel's forward the window's share."""
    import jax
    import jax.numpy as jnp

    del bias
    seg, pos = batch["seg"], batch["pos"]
    kept = jax.checkpoint_policies.save_only_these_names(*seq_attention.KEPT)
    with scope("seqrec.embed"):
        x = params["embed"][batch["tokens"]]
    stats = []
    for (kind, n), w in zip(c.runs, params["runs"]):
        def turn(x, iw, kind=kind):
            i, w = iw
            return _layer(_cast_in_loop(w, c, i), x, seg, pos, c, kind)

        keep = kept if kind == "global" else None
        with scope("seqrec.stack"):
            x, s = jax.lax.scan(
                lambda x, iw: jax.checkpoint(turn, policy=keep)(x, iw), x,
                (jnp.arange(n), w))
        stats.append(s)
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *stats)


def _head_logits(params, x, c: SmallThinkerConfig):
    """The untied head: the final norm, then W_head."""
    return _mm(_rms(x, params["final_norm"], c.rms_norm_eps),
               params["head"], c)


def loss_fn(params, bias, batch, c: SmallThinkerConfig):
    """CE(next item), a mean over the real targets, and the step's
    records; ``batch``: tokens, seg, pos, tgt1 [B, S] int32."""
    import jax.numpy as jnp

    x, stats = _stack(params, bias, batch, c)
    n = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
    ce = _chunked_ce(lambda x: _head_logits(params, x, c), x,
                     batch["tgt1"], c) / n
    return ce, {"loss": ce, "moe": stats}


def _next_logits(params, bias, batch, n, c: SmallThinkerConfig):
    """The newest ``seq_len`` items, one segment, through the same
    stack: the window layers see their window there too."""
    x, _ = _stack(params, bias, batch, c)
    return _head_logits(params, x[0, n - 1], c)


def attn_kept_bytes(c: SmallThinkerConfig) -> int:
    """Bytes a step keeps for the backward pass on account of the
    global turns' policy: per global layer and sequence the kernel's
    output in the products' dtype and a float32 a row and head."""
    rows = (sum(n for kind, n in c.runs if kind == "global")
            * c.seqs_per_step * c.seq_len)
    return rows * c.num_attention_heads * (
        c.head_dim * _dt(c).itemsize + 4)


# -- the declaration ----------------------------------------------------------


BATCH_KEYS = ("tokens", "seg", "pos", "tgt1")

BACKBONE = seq_backbone.build(
    SmallThinkerConfig, param_shapes=param_shapes,
    bias_shape=lambda c: (c.num_hidden_layers, c.router_experts),
    group_squares=group_squares, loss_fn=loss_fn,
    logits=lambda params, bias, batch, c: (_head_logits(
        params, _stack(params, bias, batch, c)[0], c),),
    next_logits=_next_logits, heads=("loss",), batch_keys=BATCH_KEYS,
    fit_attrs=lambda c: {
        "window_layers": sum(c.sliding_window_layout),
        "global_layers": c.num_hidden_layers - sum(c.sliding_window_layout),
        "attn_kept_bytes": attn_kept_bytes(c)})

n_params = BACKBONE.n_params    # benchmark/tests/test_smallthinker_layers.py
