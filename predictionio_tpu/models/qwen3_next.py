"""A ``qwen3_next`` hybrid block stack as the ``sequentialrec`` backbone.

The item catalog takes the place of the token vocabulary. Every
equation is the published block's (Qwen3-Next-80B-A3B-Instruct,
``config.json``, ``model_type qwen3_next``); x is the residual stream,
float32, and ``norm`` an RMS norm with gain 1 + w, w initialised 0 (the
family's zero-centred norm):

- **Layer**: x ← x + mixer(norm(x)); x ← x + moe(norm(x)). Layer i's
  mixer is gated full attention where (i + 1) % ``full_attention_interval``
  = 0, else Gated DeltaNet: three ``linear`` layers, then one ``full``.
  Every layer is an expert layer.
- **Gated DeltaNet** (``linear_num_key_heads`` key heads,
  ``linear_num_value_heads`` value heads of ``linear_*_head_dim``):
  [q, k, v, z] = x W_qkvz (a key head's q, its k, its value heads' v,
  then their z), [b, a] = x W_ba (a key head's value heads' b, then
  their a). [q, k, v] ← SiLU(conv([q, k, v])): depthwise, causal,
  ``linear_conv_kernel_dim`` taps, no bias; on PACKED histories a tap
  that reaches before its segment's first row is zero. q ← q/‖q‖/√d_k,
  k ← k/‖k‖ per head (‖·‖² + 1e-6 under the root); value head h reads
  key head h ÷ (H_v ÷ H_k). β = σ(b), g = −exp(A_log) · softplus(a +
  dt_bias) per value head, and the gated delta rule along each segment
  (:mod:`predictionio_tpu.ops.gated_delta`): S ← e^g S; δ = β (v − Sᵀk);
  S ← S + k δᵀ; o = Sᵀq, S = 0 at a segment's first row. Then
  o ← rmsnorm(o) ⊙ w_o ⊙ SiLU(z) per head (gain w_o initialised 1, no
  ``1 +``) and W_out.
- **Gated full attention** (H query heads over Hkv key-value heads of
  D): [q, gate] = x W_q (a head's query, then its gate), k = x W_k,
  v = x W_v, no bias; q ← norm(q), k ← norm(k) per head over D; RoPE
  (rotate halves) on the FIRST D · ``partial_rotary_factor`` dims of a
  head only, positions counted from the start of each segment; scores
  q·k/√D, causal AND inside one segment; query head h reads key-value
  head h ÷ (H ÷ Hkv); out = (attn ⊙ σ(gate)) W_o.
- **Experts**: m = norm(x′); p = softmax(m W_r) in float32 over the
  router's ``num_experts × ep_size`` experts; top-k, the k renormalised
  to sum 1 — the softmax over the SELECTED logits; y = Σ_{e ∈ ids}
  gate_e · W_d^e(silu(W_g^e m) ⊙ W_u^e m) + σ(m · w_s) · shared(m).
  No router bias, no scaling, no auxiliary loss. THIS chip holds
  experts ``ep_rank·n … (ep_rank+1)·n − 1`` and adds only their part
  (:mod:`predictionio_tpu.ops.moe_dispatch`), the shared expert whole.
  The expert half runs ``token_chunk`` rows at a time, each chunk
  recomputed in the backward pass: with 512 experts and 32 held, 15 of
  16 (token, expert) pairs are of absent experts, and the dispatch's
  pair buffer over a whole 16,384-row step would be the step's largest
  array.
- **Head**: norm_final(x_L) W_head, untied from the embedding. Loss:
  the cross-entropy of the next item, a mean over the real targets.
  No multi-token-prediction module.

A run of consecutive layers of one kind is ONE scanned body over its
stacked weights (``params["runs"][r]``): the published 48 layers are
24 runs, the benchmark's period of four two (3 × linear, 1 × full).
A layer turn of that scan is one ``jax.checkpoint``: the backward pass
recomputes the turn — projections, convolution, attention, the expert
half — from the residual stream that entered it, EXCEPT the
recurrence: the turn's policy keeps the rule's output and the states
that enter the blocks of its walk (``gated_delta.KEPT``;
``gdn_kept_bytes`` on the ``seqrec.fit`` span), so a step walks a
linear layer forward twice (the forward pass, then block by block
inside the rule's backward), not three times. In a full-attention run
the names do not occur and nothing is kept.

Precision, packing, the pieces any backbone has, the train step and
the verb's spans are :mod:`predictionio_tpu.models.seq_backbone`'s.
Here besides: the convolution's taps, the norms of q and k, β, g and
the WHOLE recurrence — its state and every product of the scan — are
float32. The train step's router bias is carried as zeros and never
moves (``bias_update_rate`` 0): this router has none. The file ends in
the backbone's declaration (:func:`seq_backbone.build` makes the rest
of it).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, ClassVar, Dict, Optional, Tuple

import numpy as np

from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models.lfm2_moe import conv_masked_taps
from predictionio_tpu.models.seq_backbone import (
    _cast_in_loop, _chunked_ce, _dt, _experts, _mm, _rms, _rope, _route,
    _stacked, _swiglu_shapes, scope)
from predictionio_tpu.ops import gated_delta

KINDS = ("linear", "full")


@dataclass(frozen=True)
class Qwen3NextConfig(seq_backbone.ArchitectureConfig):
    model_type: ClassVar[str] = "qwen3_next"
    #: what the published config may say and this file can honour
    _REQUIRED: ClassVar[Dict[str, Any]] = {
        "model_type": "qwen3_next", "decoder_sparse_step": 1,
        "hidden_act": "silu", "mlp_only_layers": [], "norm_topk_prob": True,
        "rope_scaling": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    #: published keys that size nothing here: the dense width no layer
    #: has, and a limit
    _UNUSED: ClassVar[tuple] = ("intermediate_size",
                                "max_position_embeddings")
    _HELD: ClassVar[str] = "num_experts"
    #: this router has no bias: the step's rule moves it by nothing
    bias_update_rate: ClassVar[float] = 0.0
    hidden_size: int = 2048
    num_hidden_layers: int = 4
    #: layer i is full attention where (i + 1) % this = 0
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    #: routed experts HELD here; the router is ``ep_size`` times as wide
    num_experts: int = 512
    ep_size: int = 1
    ep_rank: int = 0
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-6
    vocab_size: int = 151936
    # -- the training job (not in the published config) ----------------
    seq_len: int = 16384
    seqs_per_step: int = 1
    #: rows of a chunk of the recurrence's scan
    gdn_chunk: int = 64
    clip_norm: float = 1.0
    init_std: float = 0.02
    matmul_dtype: str = "bfloat16"
    #: most query rows an attention tile holds; tokens per chunk of the
    #: expert half and of the loss: what bounds the program's temporaries
    attn_block: int = 512
    token_chunk: int = 4096

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]) -> "Qwen3NextConfig":
        c = super().from_architecture(arch)
        if c.full_attention_interval < 1:
            raise ValueError(f"full_attention_interval "
                             f"{c.full_attention_interval} below 1")
        if c.linear_num_value_heads % c.linear_num_key_heads:
            raise ValueError(f"{c.linear_num_value_heads} value heads over "
                             f"{c.linear_num_key_heads} key heads")
        if c.num_attention_heads % c.num_key_value_heads:
            raise ValueError(f"{c.num_attention_heads} query heads over "
                             f"{c.num_key_value_heads} key-value heads")
        if c.rotary_dim % 2 or not 0 < c.rotary_dim <= c.head_dim:
            raise ValueError(f"partial_rotary_factor "
                             f"{c.partial_rotary_factor} of a head of "
                             f"{c.head_dim} rotates {c.rotary_dim} dims")
        if c.num_experts_per_tok > c.router_experts:
            raise ValueError(f"top-{c.num_experts_per_tok} of a router of "
                             f"{c.router_experts}")
        if c.gdn_chunk < 1 or c.seq_len % c.gdn_chunk:
            raise ValueError(f"chunks of {c.gdn_chunk} rows do not divide "
                             f"a sequence of {c.seq_len}")
        return c

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def kinds(self) -> Tuple[str, ...]:
        return tuple(KINDS[(i + 1) % self.full_attention_interval == 0]
                     for i in range(self.num_hidden_layers))

    @property
    def chunk(self) -> Optional[int]:
        """Rows of a chunk of the linear layers' scan; None where no
        layer carries a state."""
        return self.gdn_chunk if "linear" in self.kinds else None

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """(kind, layers) of each run of consecutive layers of one
        kind, in stack order."""
        return tuple((kind, len(list(layers)))
                     for kind, layers in itertools.groupby(self.kinds))


# -- parameters ---------------------------------------------------------------


def _layer_shapes(c: Qwen3NextConfig, kind: str) -> Dict[str, Any]:
    d = c.hidden_size
    out: Dict[str, Any] = {
        "op_norm": (d,), "ffn_norm": (d,), "router": (d, c.router_experts),
        "shared": _swiglu_shapes(d, c.shared_expert_intermediate_size),
        "shared_gate": (d,),
        "experts": _swiglu_shapes(d, c.moe_intermediate_size,
                                  (c.num_experts,))}
    if kind == "linear":
        Hv = c.linear_num_value_heads
        keys = c.linear_num_key_heads * c.linear_key_head_dim
        values = Hv * c.linear_value_head_dim
        out["gdn"] = {
            "w_qkvz": (d, 2 * keys + 2 * values), "w_ba": (d, 2 * Hv),
            "taps": (c.linear_conv_kernel_dim, 2 * keys + values),
            "A_log": (Hv,), "dt_bias": (Hv,),
            "out_norm": (c.linear_value_head_dim,), "w_out": (values, d)}
    else:
        D = c.head_dim
        q, kv = c.num_attention_heads * D, c.num_key_value_heads * D
        out["attn"] = {"wq": (d, 2 * q), "wk": (d, kv), "wv": (d, kv),
                       "q_norm": (D,), "k_norm": (D,), "wo": (q, d)}
    return out


def param_shapes(c: Qwen3NextConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``runs[r]`` carries a leading
    layer axis: a run's identical layers are ONE scanned body."""
    return {"embed": (c.vocab_size, c.hidden_size),
            "runs": [_stacked(_layer_shapes(c, kind), n)
                     for kind, n in c.runs],
            "final_norm": (c.hidden_size,),
            "head": (c.hidden_size, c.vocab_size)}


def init_leaf(name: str, key, shape):
    """What of the tree is not normal(0, init_std) or a unit gain: the
    zero-centred norms' w (every ``*norm`` but the recurrence's output
    norm) zero, ``A_log`` the log of uniform(0, 16), ``dt_bias`` one —
    the family's initialiser. None: the shared rule's."""
    import jax
    import jax.numpy as jnp

    leaf = name.split(".")[-1]
    if leaf.endswith("norm") and leaf != "out_norm":
        return jnp.zeros(shape, jnp.float32)
    if leaf == "A_log":
        return jnp.log(jnp.maximum(jax.random.uniform(
            key, shape, jnp.float32, 0.0, 16.0), 1e-6))
    if leaf == "dt_bias":
        return jnp.ones(shape, jnp.float32)
    return None


def group_of(name: str) -> str:
    """The parameter group a leaf's gradient norm is recorded under:
    by part, over all the layers that have it (the shared expert with
    its gate)."""
    parts = name.split(".")
    if parts[-1].endswith("norm"):
        return "norms"
    if parts[0] in ("embed", "head"):
        return parts[0]
    return "shared" if parts[2] == "shared_gate" else parts[2]


def group_squares(grads) -> Dict[str, Any]:
    """Σ g² per parameter group of a gradient tree."""
    return seq_backbone.squares_by_group(grads, group_of)


# -- the block ----------------------------------------------------------------


def _norm(x, w, c: Qwen3NextConfig):
    """The zero-centred RMS norm: gain 1 + w."""
    return _rms(x, 1.0 + w, c.rms_norm_eps)


def _l2(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv(x, taps, pos):
    """The causal depthwise convolution in float32: x [B, S, …];
    ``taps`` [L, …], tap j reads the row L − 1 − j before; ``pos``
    [B, S], a row's position inside its segment — a tap that reaches
    further back than that is zero."""
    import jax.numpy as jnp

    S, L = x.shape[1], taps.shape[0]
    lead = ((0, 0),) * (x.ndim - 2)
    out = x * taps[L - 1]
    for back in range(1, L):
        before = jnp.pad(x, ((0, 0), (back, 0)) + lead)[:, :S]
        reach = (pos >= back).reshape(pos.shape + (1,) * (x.ndim - 2))
        out = out + jnp.where(reach, before, 0.0) * taps[L - 1 - back]
    return out


def _gdn(w, x, seg, pos, c: Qwen3NextConfig):
    """x [B, S, d] (normed) → [B, S, d]: Gated DeltaNet."""
    import jax
    import jax.numpy as jnp

    B, S, _ = x.shape
    Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv, r = c.linear_key_head_dim, c.linear_value_head_dim, Hv // Hk
    mixed = 2 * dk + r * dv                 # a key head's q, k and v's
    qkvz = _mm(x, w["w_qkvz"], c).reshape(B, S, Hk, mixed + r * dv)
    ba = _mm(x, w["w_ba"], c).reshape(B, S, Hk, 2 * r)
    with scope("seqrec.gdn.conv"):
        qkv = jax.nn.silu(_conv(
            qkvz[..., :mixed],
            w["taps"].astype(jnp.float32).reshape(-1, Hk, mixed), pos))
    q = _l2(qkv[..., :dk]) * (1.0 / np.sqrt(dk))
    k = _l2(qkv[..., dk:2 * dk])
    v = qkv[..., 2 * dk:].reshape(B, S, Hv, dv)
    z = qkvz[..., mixed:].reshape(B, S, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(B, S, Hv))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(B, S, Hv) + w["dt_bias"])
    with scope("seqrec.gdn.scan"):
        o = gated_delta.gated_delta_rule(q, k, v, g, beta, seg, c.gdn_chunk)
    o = _rms(o, w["out_norm"], c.rms_norm_eps) * jax.nn.silu(z)
    return _mm(o.reshape(B, S, Hv * dv), w["w_out"], c)


def _gated_attention(w, x, seg, pos, c: Qwen3NextConfig):
    """x [B, S, d] (normed) → [B, S, d], one sequence at a time."""
    import jax
    import jax.numpy as jnp

    H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    rot = c.rotary_dim

    def rotate(a, pos):
        return jnp.concatenate([_rope(a[..., :rot], pos[:, None],
                                      c.rope_theta), a[..., rot:]], -1)

    def one(args):
        x, seg, pos = args
        S = x.shape[0]
        qg = _mm(x, w["wq"], c).reshape(S, H, 2 * D)
        q = rotate(_norm(qg[..., :D], w["q_norm"], c), pos)
        k = rotate(_norm(_mm(x, w["wk"], c).reshape(S, Hkv, D),
                         w["k_norm"], c), pos)
        v = _mm(x, w["wv"], c).reshape(S, Hkv, D)
        with scope("seqrec.gqa.attention"):
            out = seq_backbone.attention(
                q.astype(_dt(c)), k.astype(_dt(c)), v.astype(_dt(c)), seg,
                c, 1.0 / np.sqrt(D))
        out = (out * jax.nn.sigmoid(qg[..., D:])).astype(_dt(c))
        return jnp.tensordot(out, w["wo"].astype(_dt(c)).reshape(H, D, -1),
                             2, preferred_element_type=jnp.float32)

    return jax.lax.map(one, (x, seg, pos))


def _moe(w, m, valid, c: Qwen3NextConfig):
    """m [T, d] float32 (normed) → this chip's part of the expert
    layer's result [T, d] and what the step records of the routing:
    route and experts ``token_chunk`` rows at a time, a chunk's pair
    buffer recomputed in the backward pass and never kept."""
    import jax
    import jax.numpy as jnp

    T, d = m.shape
    n = min(c.token_chunk, T)
    if T % n:
        raise ValueError(f"{T} tokens are no multiple of token_chunk {n}")

    @jax.checkpoint
    def chunk(args):
        m, valid = args
        gates, plan, stats = _route(w["router"], m, valid, None, c,
                                    softmax=True)
        return _experts(w, m, gates, plan, c), stats

    y, stats = jax.lax.map(chunk, (m.reshape(-1, n, d),
                                   valid.reshape(-1, n)))
    stats = {k: v.sum(axis=0) for k, v in stats.items()}
    held = stats["load"][jnp.asarray(c.held)]
    stats["load_max_over_mean"] = held.max() / jnp.maximum(held.mean(), 1e-9)
    return y.reshape(T, d), stats


def _layer(w, x, seg, pos, c: Qwen3NextConfig, kind: str):
    """One layer on the residual stream x [B, S, d] float32."""
    B, S, d = x.shape
    if kind == "linear":
        with scope("seqrec.gdn"):
            x = x + _gdn(w["gdn"], _norm(x, w["op_norm"], c), seg, pos, c)
    else:
        with scope("seqrec.gqa"):
            x = x + _gated_attention(w["attn"], _norm(x, w["op_norm"], c),
                                     seg, pos, c)
    with scope("seqrec.norm"):
        m = _norm(x, w["ffn_norm"], c).reshape(B * S, d)
    y, stats = _moe(w, m, seg.reshape(-1) > 0, c)
    with scope("seqrec.residual"):
        return x + y.reshape(B, S, d), stats


def _stack(params, bias, batch, c: Qwen3NextConfig):
    """Embedding and the stack: x_L [B, S, d] and the layers' routing
    records (leading axis: layer, in stack order). ``bias`` is the
    step's zero router bias: nothing reads it. A layer turn is one
    ``jax.checkpoint`` that keeps what the recurrence names
    (:data:`gated_delta.KEPT`) and recomputes the rest."""
    import jax
    import jax.numpy as jnp

    del bias
    seg, pos = batch["seg"], batch["pos"]
    kept = jax.checkpoint_policies.save_only_these_names(*gated_delta.KEPT)
    with scope("seqrec.embed"):
        x = params["embed"][batch["tokens"]]
    stats = []
    for (kind, n), w in zip(c.runs, params["runs"]):
        def turn(x, iw, kind=kind):
            i, w = iw
            return _layer(_cast_in_loop(w, c, i, aside=("router", "taps")),
                          x, seg, pos, c, kind)

        with scope("seqrec.stack"):
            x, s = jax.lax.scan(
                lambda x, iw: jax.checkpoint(turn, policy=kept)(x, iw), x,
                (jnp.arange(n), w))
        stats.append(s)
    return x, jax.tree.map(lambda *a: jnp.concatenate(a), *stats)


def _head_logits(params, x, c: Qwen3NextConfig):
    """The untied head: the final norm, then W_head."""
    return _mm(_norm(x, params["final_norm"], c), params["head"], c)


def loss_fn(params, bias, batch, c: Qwen3NextConfig):
    """CE(next item), a mean over the real targets, and the step's
    records; ``batch``: tokens, seg, pos, tgt1 [B, S] int32."""
    import jax.numpy as jnp

    x, stats = _stack(params, bias, batch, c)
    n = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
    ce = _chunked_ce(lambda x: _head_logits(params, x, c), x,
                     batch["tgt1"], c) / n
    return ce, {"loss": ce, "moe": stats}


def _next_logits(params, bias, batch, n, c: Qwen3NextConfig):
    """The newest ``seq_len`` items, one segment, through the same
    stack: the recurrence from a zero state over the whole history."""
    x, _ = _stack(params, bias, batch, c)
    return _head_logits(params, x[0, n - 1], c)


def gdn_kept_bytes(c: Qwen3NextConfig) -> int:
    """Bytes a step keeps for the backward pass on account of the
    turn's policy: per linear layer and sequence the recurrence's
    output and the state that enters each block of its walk, float32."""
    H, dk, dv = (c.linear_num_value_heads, c.linear_key_head_dim,
                 c.linear_value_head_dim)
    blocks = c.seq_len // gated_delta.block_rows(c.gdn_chunk, c.seq_len)[1]
    return (c.kinds.count("linear") * c.seqs_per_step * 4
            * (c.seq_len * H * dv + blocks * H * dk * dv))


def gdn_walk(c: Qwen3NextConfig) -> dict:
    """The ``seqrec.fit`` span's account of the walk over a block's
    chunks: the form it takes at this configuration's shapes
    (``gated_delta.walk_form``: ``"kernel"`` or ``"scan"``) and the
    chunk steps a train step walks — per linear layer and sequence
    every chunk forward twice (the pass; block by block inside the
    rule's backward) and in reverse once."""
    C = gated_delta.block_rows(c.gdn_chunk, c.seq_len)[0]
    return {"gdn_walk": gated_delta.walk_form(
                C, c.linear_key_head_dim, c.linear_value_head_dim),
            "gdn_walk_chunk_steps": (c.kinds.count("linear")
                                     * c.seqs_per_step * 3
                                     * (c.seq_len // C))}


# -- the declaration ----------------------------------------------------------


BATCH_KEYS = ("tokens", "seg", "pos", "tgt1")

BACKBONE = seq_backbone.build(
    Qwen3NextConfig, param_shapes=param_shapes,
    bias_shape=lambda c: (c.num_hidden_layers, c.router_experts),
    group_squares=group_squares, loss_fn=loss_fn,
    logits=lambda params, bias, batch, c: (_head_logits(
        params, _stack(params, bias, batch, c)[0], c),),
    next_logits=_next_logits, heads=("loss",), batch_keys=BATCH_KEYS,
    init_leaf=init_leaf,
    pack_attrs=lambda packed, c: {"conv_masked_taps": conv_masked_taps(
        packed.pos, packed.seg, c.linear_conv_kernel_dim)},
    fit_attrs=lambda c: {
        "linear_layers": c.kinds.count("linear"),
        "full_layers": c.kinds.count("full"),
        "gdn_kept_bytes": gdn_kept_bytes(c), **gdn_walk(c)})

n_params = BACKBONE.n_params    # benchmark/tests/test_qwen3next_layers.py
