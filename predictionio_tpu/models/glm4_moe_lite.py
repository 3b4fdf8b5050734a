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
(:func:`pack_histories`); id 0 is PAD.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, fields
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.ops import moe_dispatch, seq_attention

#: what the published config may say and this file can honour
_REQUIRED = {"hidden_act": "silu", "attention_bias": False, "n_group": 1,
             "topk_group": 1, "topk_method": "noaux_tc",
             "rope_scaling": None, "tie_word_embeddings": False,
             "partial_rotary_factor": 1, "model_type": "glm4_moe_lite"}
#: published keys that size nothing here (a limit, not a shape)
_UNUSED = ("max_position_embeddings",)


@dataclass(frozen=True)
class GlmConfig:
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
        """The ``architecture`` object of the algorithm's parameters:
        the published config's keys (and this class's own)."""
        for key, want in _REQUIRED.items():
            if key in arch and arch[key] != want:
                raise ValueError(f"architecture.{key} = {arch[key]!r}: "
                                 f"only {want!r} is implemented")
        if arch.get("num_key_value_heads",
                    arch.get("num_attention_heads")) != arch.get(
                        "num_attention_heads"):
            raise ValueError("latent attention has one key per head")
        if arch.get("num_nextn_predict_layers", 1) != 1:
            raise ValueError("exactly one MTP module is implemented")
        names = {f.name for f in fields(cls)}
        unknown = set(arch) - names - set(_REQUIRED) - set(_UNUSED) - {
            "num_key_value_heads"}
        if unknown:
            raise ValueError(f"unknown architecture keys {sorted(unknown)}")
        return cls(**{k: v for k, v in arch.items() if k in names})

    @property
    def router_experts(self) -> int:
        return self.n_routed_experts * self.ep_size

    @property
    def held(self) -> Tuple[int, ...]:
        lo = self.ep_rank * self.n_routed_experts
        return tuple(range(lo, lo + self.n_routed_experts))

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


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


def _swiglu_shapes(d: int, f: int, lead: tuple = ()) -> Dict[str, tuple]:
    return {"wg": lead + (d, f), "wu": lead + (d, f), "wd": lead + (f, d)}


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


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def param_shapes(c: GlmConfig) -> Dict[str, Any]:
    """The parameter tree as shapes. ``dense`` and ``moe`` carry a
    leading layer axis: the identical layers are ONE scanned body."""
    import jax

    d = c.hidden_size

    def stacked(tree, n):
        return jax.tree.map(lambda s: (n,) + s, tree, is_leaf=_is_shape)

    return {
        "embed": (c.vocab_size, d),
        "dense": stacked(_block_shapes(c, True), c.first_k_dense_replace),
        # the expert layers, then the MTP module's block
        "moe": stacked(_block_shapes(c, False), c.n_moe_layers + 1),
        "final_norm": (d,),
        "head": (d, c.vocab_size),
        "mtp": {"enorm": (d,), "hnorm": (d,), "eh_proj": (2 * d, d),
                "final_norm": (d,)},
    }


def n_params(c: GlmConfig) -> int:
    import jax

    return sum(int(np.prod(s)) for s in
               jax.tree.leaves(param_shapes(c), is_leaf=_is_shape))


def _path_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


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


def init_state(c: GlmConfig, seed: int, with_optimizer: bool = False):
    """(params, router bias) made ON the device from the seed, by one
    jitted program: normal(0, init_std) matrices, unit norm gains, zero
    bias, the PAD row of the embedding zero. ``with_optimizer``: Adam's
    zeroed state too — (params, opt_state, bias), still one program."""
    return _init_compiled(c, with_optimizer)(np.uint32(seed % (1 << 32)))


@functools.lru_cache(maxsize=4)
def _init_compiled(c: GlmConfig, with_optimizer: bool):
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.seq_rec import _make_tx

    shapes = param_shapes(c)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def init(seed):
        # the hardware generator: 706 M normals through threefry would
        # cost more to compile than to draw
        keys = jax.random.split(jax.random.key(seed, impl="rbg"),
                                len(leaves))
        out = []
        for key, (path, shape) in zip(keys, leaves):
            if _path_name(path).endswith("norm"):
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(c.init_std * jax.random.normal(
                    key, shape, jnp.float32))
        params = jax.tree_util.tree_unflatten(treedef, out)
        params["embed"] = params["embed"].at[0].set(0.0)
        bias = jnp.zeros((c.n_moe_layers + 1, c.router_experts),
                         jnp.float32)
        if with_optimizer:
            return params, _make_tx().init(params), bias
        return params, bias

    return jax.jit(init)


# -- packing ------------------------------------------------------------------


class Packed(NamedTuple):
    tokens: np.ndarray    # [N, S] int32 item ids, 0 = PAD
    seg: np.ndarray       # [N, S] int32 segment of the slot, 0 = PAD
    pos: np.ndarray       # [N, S] int32 position inside the segment
    tgt1: np.ndarray      # [N, S] int32 next item of the segment, 0 = none
    tgt2: np.ndarray      # [N, S] int32 the item after it, 0 = none
    counters: Dict[str, int]


def _targets(tokens: np.ndarray, seg: np.ndarray, ahead: int) -> np.ndarray:
    out = np.zeros_like(tokens)
    same = (seg[:, ahead:] == seg[:, :-ahead]) & (seg[:, :-ahead] > 0)
    out[:, :-ahead] = np.where(same, tokens[:, ahead:], 0)
    return out


def pack_histories(histories: Sequence[Sequence[int]], seq_len: int,
                   seqs_per_step: int = 1, seed: int = 0) -> Packed:
    """Histories (item ids ≥ 1, oldest first) → ``seq_len``-slot
    sequences with segment ids. A history longer than a sequence is
    cut into ``seq_len`` pieces; pieces go whole, longest first, into
    the first of ⌈tokens / seq_len⌉ sequences with room, and one that
    fits nowhere whole is cut to fill the gaps. Each piece is a
    segment: attention, RoPE positions and targets stay inside it. The
    sequence count is padded to a multiple of ``seqs_per_step`` and
    the order shuffled by ``seed``."""
    S = int(seq_len)
    pieces: List[np.ndarray] = []
    n_hist = n_split = 0
    for h in histories:
        h = np.asarray(h, np.int32)
        h = h[h > 0]
        if h.size < 2:
            continue
        n_hist += 1
        n_split += h.size > S
        pieces += [h[a:a + S] for a in range(0, h.size, S)]
    if not pieces:
        raise ValueError("no trainable history (all shorter than 2)")
    total = sum(p.size for p in pieces)
    n_seq = -(-total // S)
    room = np.full(n_seq, S, np.int64)
    bins: List[List[np.ndarray]] = [[] for _ in range(n_seq)]
    left: List[np.ndarray] = []
    for p in sorted(pieces, key=lambda p: -p.size):
        fit = np.flatnonzero(room >= p.size)
        if fit.size:
            bins[fit[0]].append(p)
            room[fit[0]] -= p.size
        else:
            left.append(p)
    for p in left:
        n_split += 1
        while p.size:
            b = int(np.argmax(room > 0))
            take = int(min(room[b], p.size))
            bins[b].append(p[:take])
            room[b] -= take
            p = p[take:]
    n_all = -(-n_seq // seqs_per_step) * seqs_per_step
    tokens = np.zeros((n_all, S), np.int32)
    seg = np.zeros((n_all, S), np.int32)
    pos = np.zeros((n_all, S), np.int32)
    for b, rows in enumerate(bins):
        at = 0
        for j, p in enumerate(rows):
            tokens[b, at:at + p.size] = p
            seg[b, at:at + p.size] = j + 1
            pos[b, at:at + p.size] = np.arange(p.size)
            at += p.size
    order = np.random.default_rng(seed).permutation(n_all)
    tokens, seg, pos = tokens[order], seg[order], pos[order]
    tgt1, tgt2 = _targets(tokens, seg, 1), _targets(tokens, seg, 2)
    sizes = np.asarray([p.size for rows in bins for p in rows], np.int64)
    return Packed(tokens, seg, pos, tgt1, tgt2, {
        "histories": n_hist, "split": int(n_split), "sequences": n_all,
        "slots": n_all * S, "real_tokens": int(total),
        # (query, key) pairs causal attention inside the segments sees
        "attn_pairs": int((sizes * (sizes + 1) // 2).sum()),
        "targets": int((tgt1 > 0).sum()),
        "mtp_targets": int((tgt2 > 0).sum())})


# -- the block ----------------------------------------------------------------


def _attn_tiles(c: GlmConfig, S: int) -> Tuple[int, int]:
    """(query rows, keys) of an attention tile on ``S`` slots: the
    most that ``attn_block`` and the kernels' key tile allow and that
    divide S."""
    return math.gcd(c.attn_block, S), math.gcd(seq_attention.KEY_TILE, S)


def _dt(c: GlmConfig):
    import jax.numpy as jnp

    return jnp.dtype(c.matmul_dtype)


def _mm(x, w, c: GlmConfig):
    """Operands in the matmul dtype, float32 accumulation and result."""
    import jax.numpy as jnp

    return jnp.dot(x.astype(_dt(c)), w.astype(_dt(c)),
                   preferred_element_type=jnp.float32)


def _rms(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, pos, theta: float):
    """Rotate-half RoPE over the last axis, in float32; ``pos``
    broadcasts against x's leading axes."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, seg, c: GlmConfig):
    """Causal, segment-masked attention of ONE sequence: [S, H, D]
    each → [S, H, Dv] in v's dtype. Tiles of at most ``attn_block``
    query rows, and only those between a block's earliest segment and
    the diagonal (:mod:`predictionio_tpu.ops.seq_attention`)."""
    return seq_attention.segment_attention(
        q, k, v, seg, *_attn_tiles(c, q.shape[0]),
        1.0 / np.sqrt(c.qk_head_dim))


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
        k_r = _rope(ckv[:, c.kv_lora_rank:], pos, c.rope_theta)
        kv = _mm(_rms(ckv[:, :c.kv_lora_rank], w["kv_norm"], eps),
                 w["wkvb"], c).reshape(S, H, dn + dv)
        q = jnp.concatenate(
            [q[..., :dn], _rope(q[..., dn:], pos[:, None], c.rope_theta)],
            -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (S, H, dr))],
            -1)
        with jax.named_scope("seqrec.mla.attention"):
            out = _attention(q.astype(_dt(c)), k.astype(_dt(c)),
                             kv[..., dn:].astype(_dt(c)), seg, c)
        # W_o takes the heads' outputs as attention leaves them: ONE
        # array for both backward passes to keep, not it and a reshape
        return jnp.tensordot(out, w["wo"].astype(_dt(c)).reshape(H, dv, -1),
                             2, preferred_element_type=jnp.float32)

    return jax.lax.map(one, (x, seg, pos))


def _swiglu(w, x, c: GlmConfig):
    """W_d(silu(W_g x) ⊙ W_u x), ``token_chunk`` tokens at a time; the
    wide intermediates are recomputed in the backward pass, never
    kept for all the tokens at once."""
    import jax

    @jax.checkpoint
    def chunk(x):
        return _mm(jax.nn.silu(_mm(x, w["wg"], c)) * _mm(x, w["wu"], c),
                   w["wd"], c)

    d = x.shape[-1]
    rows = x.reshape(-1, d)
    n = min(c.token_chunk, rows.shape[0])
    if rows.shape[0] % n:
        raise ValueError(f"{rows.shape[0]} tokens are no multiple of "
                         f"token_chunk {n}")
    return jax.lax.map(chunk, rows.reshape(-1, n, d)).reshape(x.shape)


def _moe(w, x, valid, bias, c: GlmConfig):
    """x [T, d] float32 (normed) → this chip's part of the layer's
    result [T, d], and what the step records of the routing."""
    import jax
    import jax.numpy as jnp

    E, k = c.router_experts, c.num_experts_per_tok
    with jax.named_scope("seqrec.moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            x, w["router"], precision=jax.lax.Precision.HIGHEST))
        ids, gates = moe_dispatch.route(
            scores, bias, k, c.routed_scaling_factor, c.norm_topk_prob)
        p = moe_dispatch.plan(ids, c.held, E, valid)
        load = jnp.zeros(E, jnp.float32).at[ids.reshape(-1)].add(
            jnp.repeat(valid, k).astype(jnp.float32))
    routed = moe_dispatch.experts_swiglu(
        x.astype(_dt(c)), w["experts"]["wg"].astype(_dt(c)),
        w["experts"]["wu"].astype(_dt(c)), w["experts"]["wd"].astype(_dt(c)),
        gates, p)
    with jax.named_scope("seqrec.ffn"):
        shared = _swiglu(w["shared"], x, c)
    held = load[jnp.asarray(c.held)]
    return routed + shared, {
        "load": load, "pairs": valid.sum() * k, "pairs_here": p.pairs_here,
        "dropped": p.pairs_here - p.rows,
        "load_max_over_mean": held.max() / jnp.maximum(held.mean(), 1e-9)}


def _block(w, x, seg, pos, bias, c: GlmConfig):
    """One layer on the residual stream x [B, S, d] float32; ``bias``
    None marks a dense layer."""
    import jax

    B, S, d = x.shape
    with jax.named_scope("seqrec.mla"):
        x = x + _mla(w["attn"], _rms(x, w["attn_norm"], c.rms_norm_eps),
                     seg, pos, c)
    h = _rms(x, w["ffn_norm"], c.rms_norm_eps)
    if bias is None:
        with jax.named_scope("seqrec.ffn"):
            return x + _swiglu(w["ffn"], h, c), None
    y, stats = _moe(w, h.reshape(B * S, d), seg.reshape(-1) > 0, bias, c)
    return x + y.reshape(B, S, d), stats


def _cast_in_loop(w, c: GlmConfig, turn):
    """A scanned layer's matrices in the matmul dtype, the router's
    aside (its product is float32), cast INSIDE the loop. Left to
    itself the compiler hoists the casts out of the loop — a bfloat16
    copy of ALL the layers' weights, 0.7 GB at the cell's size, for the
    whole step — and no ``optimization_barrier`` stops it; a factor of
    one that is computed from the loop's counter ``turn`` does, in the
    same fused pass as the cast."""
    import jax.numpy as jnp

    one = jnp.where(turn >= 0, 1.0, 0.0).astype(jnp.float32)
    return {k: (v if k == "router" else _cast_in_loop(v, c, turn)
                if isinstance(v, dict)
                else (v * one).astype(_dt(c)) if v.ndim >= 2 else v)
            for k, v in w.items()}


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
    with jax.named_scope("seqrec.embed"):
        x = params["embed"][tokens]
        nxt = params["embed"][batch["tgt1"]] if mtp else None
    n, w_mtp, eps = c.n_moe_layers, params["mtp"], c.rms_norm_eps

    def dense(x, w):
        return jax.checkpoint(
            lambda w, x: _block(w, x, seg, pos, None, c)[0])(w, x), None

    def enter_mtp(x):
        with jax.named_scope("seqrec.mtp"):
            return _mm(jnp.concatenate(
                [_rms(nxt, w_mtp["enorm"], eps),
                 _rms(x, w_mtp["hnorm"], eps)], -1), w_mtp["eh_proj"], c), x

    def turn(i, w, b, x, x_last):
        w = _cast_in_loop(w, c, i)
        if mtp:
            x, x_last = jax.lax.cond(i == n, enter_mtp,
                                     lambda x: (x, x_last), x)
        x, stats = _block(w, x, seg, pos, b, c)
        return (x, x_last), stats

    def sparse(carry, iwb):
        return jax.checkpoint(turn)(*iwb, *carry)

    x, _ = jax.lax.scan(dense, x, params["dense"])
    turns = n + 1 if mtp else n
    (x, x_last), stats = jax.lax.scan(
        sparse, (x, x),
        (jnp.arange(turns), jax.tree.map(lambda a: a[:turns], params["moe"]),
         bias[:turns]))
    return (x_last, x, stats) if mtp else (x, None, stats)


def _head_logits(params, norm, x, c: GlmConfig):
    return _mm(_rms(x, norm, c.rms_norm_eps), params["head"], c)


def _chunked_ce(params, norm, x, targets, c: GlmConfig):
    """Σ cross-entropy over the real targets, the logits made
    ``token_chunk`` tokens at a time and never kept."""
    import jax
    import jax.numpy as jnp

    d = x.shape[-1]
    x, t = x.reshape(-1, d), targets.reshape(-1)
    n = min(c.token_chunk, x.shape[0])

    @jax.checkpoint
    def chunk(xt):
        x, t = xt
        logits = _head_logits(params, norm, x, c)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.where(t > 0, lse - hit, 0.0).sum()

    with jax.named_scope("seqrec.head"):
        return jax.lax.map(chunk, (x.reshape(-1, n, d),
                                   t.reshape(-1, n))).sum()


def loss_fn(params, bias, batch, c: GlmConfig):
    """CE(next item) + λ·CE_MTP(item after next) and the step's
    records; ``batch``: tokens, seg, pos, tgt1, tgt2 [B, S] int32."""
    import jax.numpy as jnp

    x, xm, stats = _stack(params, bias, batch, c)
    n1 = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
    n2 = jnp.maximum((batch["tgt2"] > 0).sum(), 1)
    ce1 = _chunked_ce(params, params["final_norm"], x, batch["tgt1"], c) / n1
    ce2 = _chunked_ce(params, params["mtp"]["final_norm"], xm,
                      batch["tgt2"], c) / n2
    return ce1 + c.mtp_loss_weight * ce2, {
        "loss": ce1, "mtp_loss": ce2, "moe": stats}


def logits_both(params, bias, batch, c: GlmConfig):
    """Both heads' float32 logits [B, S, V] for whole sequences (what
    the comparison with the reference reads)."""
    x, xm, _ = _stack(params, bias, batch, c)
    return (_head_logits(params, params["final_norm"], x, c),
            _head_logits(params, params["mtp"]["final_norm"], xm, c))


# -- the train program --------------------------------------------------------


BATCH_KEYS = ("tokens", "seg", "pos", "tgt1", "tgt2")


@functools.lru_cache(maxsize=8)
def grad_groups(c: GlmConfig) -> Tuple[str, ...]:
    """The parameter groups, in the order ``group_norms`` records."""
    import jax
    import jax.numpy as jnp

    return tuple(sorted(jax.eval_shape(group_squares, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), param_shapes(c),
        is_leaf=_is_shape))))


@functools.lru_cache(maxsize=8)
def train_program(c: GlmConfig, epochs: int):
    """``train(state, data) -> (state, records)``: ``epochs`` passes
    over ``data`` ([steps, B, S] per key) as ONE compiled program, a
    scan over epochs of a scan over steps. ``state``: params, opt_state
    (:func:`predictionio_tpu.models.seq_rec._make_tx`), bias. The
    learning rate rides in the optimizer state."""
    import jax
    import jax.numpy as jnp

    import optax

    from predictionio_tpu.models.seq_rec import _make_tx

    tx, groups = _make_tx(), grad_groups(c)

    def step(state, batch):
        params, opt_state, bias = state
        (_, rec), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, bias, batch, c)
        with jax.named_scope("seqrec.optimizer"):
            squares = group_squares(grads)
            norm = jnp.sqrt(sum(squares.values()))
            scale = jnp.minimum(1.0, c.clip_norm / (norm + 1e-6))
            grads = jax.tree.map(lambda g: g * scale, grads)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            load = rec["moe"]["load"]
            bias = bias + c.bias_update_rate * jnp.sign(
                load.mean(axis=-1, keepdims=True) - load)
        moe = rec["moe"]
        record = {
            "loss": rec["loss"], "mtp_loss": rec["mtp_loss"],
            "grad_norm": norm,
            "group_norms": jnp.stack([jnp.sqrt(squares[g])
                                      for g in groups]),
            "moe_pairs": moe["pairs"].sum(),
            "moe_pairs_here": moe["pairs_here"].sum(),
            "moe_dropped_pairs": moe["dropped"].sum(),
            "moe_load_max_over_mean": moe["load_max_over_mean"].max(),
            "router_bias_absmax": jnp.abs(bias).max(),
        }
        return (params, opt_state, bias), record

    def train(state, data):
        def epoch(state, _):
            return jax.lax.scan(step, state, data)

        if epochs == 1:
            return epoch(state, None)
        state, records = jax.lax.scan(epoch, state, None, length=epochs)
        return state, jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), records)

    return jax.jit(train, donate_argnums=(0,))


def _device_batches(packed: Packed, c: GlmConfig) -> Dict[str, Any]:
    import jax.numpy as jnp

    B = c.seqs_per_step
    return {k: jnp.asarray(getattr(packed, k).reshape(
        -1, B, packed.tokens.shape[1])) for k in BATCH_KEYS}


def glm_train(histories: Sequence[Sequence[int]], c: GlmConfig,
              epochs: int, lr: float, seed: int,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 1) -> Tuple[Dict, np.ndarray]:
    """Train on per-user item-id histories; returns the model's arrays
    on the HOST (``{"params", "bias"}``) and the loss of every step run
    in this process. Spans ``seqrec.pack`` / ``.init`` / ``.fit`` /
    ``.fetch`` land in the verb record (docs/observability.md)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.seq_rec import run_epoch_blocks
    from predictionio_tpu.utils import tracing

    if c.seq_len % min(c.attn_block, c.seq_len):
        raise ValueError("seq_len must be a multiple of attn_block")
    with tracing.span("seqrec.pack") as sp:
        packed = pack_histories(histories, c.seq_len, c.seqs_per_step, seed)
        top = max(int(packed.tokens.max()), 0)
        if top >= c.vocab_size:
            raise ValueError(f"item id {top} outside the vocabulary of "
                             f"{c.vocab_size} rows")
        for k, v in packed.counters.items():
            sp.set_attr(k, v)
        # pairs inside the tiles attention visits (one epoch and head),
        # and inside those that blocks of ``attn_block`` rows walking
        # to the diagonal would
        bq, bk = _attn_tiles(c, c.seq_len)
        sp.set_attr("attn_tile_pairs",
                    seq_attention.tile_pairs(packed.seg, bq, bk))
        sp.set_attr("attn_dense_pairs",
                    seq_attention.tile_pairs(packed.seg, bq, bq, skip=False))
    with tracing.span("seqrec.init") as sp:
        data = _device_batches(packed, c)
        params, opt_state, bias = init_state(c, seed, with_optimizer=True)
        opt_state.hyperparams["learning_rate"] = jnp.float32(lr)
        state = jax.block_until_ready(
            {"params": params, "opt_state": opt_state, "bias": bias})
        sp.set_attr("params", n_params(c))
        sp.set_attr("bytes", 16 * n_params(c))
    steps = packed.tokens.shape[0] // c.seqs_per_step
    with tracing.span("seqrec.fit", steps=steps * epochs,
                      tokens_per_step=c.seqs_per_step * c.seq_len) as sp:
        def run_block(state, n):
            out, rec = train_program(c, int(n))(
                (state["params"], state["opt_state"], state["bias"]), data)
            return (dict(zip(("params", "opt_state", "bias"), out)),
                    jax.device_get(rec))

        def set_lr(state):
            state["opt_state"].hyperparams["learning_rate"] = jnp.float32(lr)

        state, records = run_epoch_blocks(
            epochs, checkpoint_dir, checkpoint_every, state, run_block,
            set_lr)
        rec = ({k: np.concatenate([r[k] for r in records])
                for k in records[0]} if records else {})
        if rec:
            sp.set_attr("loss_first", float(rec["loss"][0]))
            sp.set_attr("mtp_loss_first", float(rec["mtp_loss"][0]))
            sp.set_attr("loss_last4", float(rec["loss"][-4:].mean()))
            sp.set_attr("losses_finite", bool(
                np.isfinite(rec["loss"]).all()
                and np.isfinite(rec["mtp_loss"]).all()))
            sp.set_attr("grad_norms_first", {
                g: float(v) for g, v in zip(grad_groups(c),
                                            rec["group_norms"][0])})
            for k in ("moe_pairs", "moe_pairs_here", "moe_dropped_pairs"):
                sp.set_attr(k, int(rec[k].sum()))
            sp.set_attr("moe_load_max_over_mean",
                        float(rec["moe_load_max_over_mean"].mean()))
            sp.set_attr("router_bias_absmax",
                        float(rec["router_bias_absmax"][-1]))
    with tracing.span("seqrec.fetch") as sp:
        host = jax.device_get({"params": state["params"],
                               "bias": state["bias"]})
        sp.set_attr("bytes", sum(a.nbytes for a in jax.tree.leaves(host)))
    del state
    return host, (rec["loss"] if rec else np.zeros(0, np.float32))


# -- serving ------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _logits_compiled(c: GlmConfig):
    import jax

    return jax.jit(lambda params, bias, batch: logits_both(
        params, bias, batch, c))


def sequence_logits(model: Dict, batch: Dict[str, np.ndarray],
                    c: GlmConfig):
    """Both heads' logits of whole packed sequences, by the program."""
    return _logits_compiled(c)(
        model["params"], model["bias"], batch)


@functools.lru_cache(maxsize=16)
def _next_compiled(c: GlmConfig):
    import jax
    import jax.numpy as jnp

    def score(params, bias, tokens, n):
        S = tokens.shape[0]
        batch = {"tokens": tokens[None],
                 "seg": (jnp.arange(S) < n).astype(jnp.int32)[None],
                 "pos": jnp.arange(S, dtype=jnp.int32)[None]}
        x, _, _ = _stack(params, bias, batch, c, mtp=False)
        return _head_logits(params, params["final_norm"], x[0, n - 1], c)

    return jax.jit(score)


def next_item_scores(model: Dict, history: Sequence[int],
                     c: GlmConfig) -> np.ndarray:
    """Scores over the vocabulary for the item after ``history`` (its
    last ``seq_len`` items, right-padded to a power-of-two bucket so
    that a handful of programs serve every length); PAD = -inf."""
    seq = [i for i in history if i > 0][-c.seq_len:]
    bucket = min(c.seq_len, max(16, 1 << max(len(seq) - 1, 0).bit_length()))
    tokens = np.zeros(bucket, np.int32)
    tokens[:len(seq)] = seq
    logits = np.array(_next_compiled(c)(
        model["params"], model["bias"], tokens, np.int32(max(len(seq), 1))))
    logits[0] = -np.inf
    return logits
