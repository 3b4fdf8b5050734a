"""A ``xing4_0`` block stack as the ``sequentialrec`` backbone.

Xing4.0-29B-A4B's ``config.json`` (``model_type xing4_0``): DeepSeek-V3's
latent attention, sigmoid router with a selection bias, shared expert
and multi-token-prediction module — :mod:`.glm4_moe_lite` carries them
equation for equation, and they are SHARED with it, not copied — with
YaRN positions, and around every sublayer a residual stream of
``hc_mult`` copies mixed by manifold-constrained hyper-connections
(:mod:`predictionio_tpu.ops.hyper_connections`; arXiv:2512.24880 after
arXiv:2409.19606).

Per token X ∈ R^{n×d}, n = ``hc_mult``:

- **Stream**: X₀ = n copies of Emb(t). After the last layer x_L = Σᵢ
  X[i]; then the final norm and the untied head.
- **Around EACH sublayer F** (attention; then dense SwiGLU or experts),
  with that sublayer's own φ ∈ R^{nd×(2n+n²)}, b, α ∈ R³:
  x̂ = vec(X)/rms(vec(X)) (eps ``rms_norm_eps``, no gain); m = x̂ φ;
  H_pre = σ(α₁·m[:n] + b[:n]); H_post = 2·σ(α₂·m[n:2n] + b[n:2n]);
  A = clamp(α₃·mat(m[2n:]) + mat(b[2n:]), ``mhc_h_res_clamp_min``,
  ``mhc_h_res_clamp_max``); M = exp(A); ``hc_sinkhorn_iters`` times
  every row ÷ (its sum + ``hc_eps``), then every column likewise;
  H_res = M. u = Σᵢ H_pre[i]·X[i]; y = F(RMSNorm_w(u)) — the sublayer's
  own pre-norm, as in the GLM block; X[i] ← Σⱼ H_res[i, j]·X[j] +
  H_post[i]·y.
- **Latent attention**: ``glm4_moe_lite._mla`` with heads of 128 + 64
  query/key dims and 128 value dims, and YaRN: per rope frequency the
  blend of θ^(−2i/64) and θ^(−2i/64)/factor by the linear ramp between
  the dims where ``beta_fast`` and ``beta_slow`` rotations fit into
  ``original_max_position_embeddings``; softmax scale (nope + rope)^−½ ·
  (0.1·``mscale_all_dim``·ln factor + 1)²; cos and sin are scaled by
  mscale(``mscale``)/mscale(``mscale_all_dim``), which only 1 is
  implemented for. Positions restart with each segment.
- **Experts, shared expert, selection bias and its rule, head, loss**:
  ``glm4_moe_lite``'s, after ``first_k_dense_replace`` dense layers.
- **MTP** (``num_nextn_predict_layers`` 0 or 1): h' = W_eh
  [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L)] enters the module's block as n
  copies, its output folds by the same sum; its own final norm, the
  same embedding and head. Its block is the LAST slice of the ``moe``
  stack and the last turn of their scanned body, as in GLM.

The turn keeps the n-copy stream that ENTERS it (``jax.checkpoint`` a
turn) and recomputes coefficients and mixes in the backward pass:
nothing of the mixer is kept between turns.

Precision: as every backbone (:mod:`.seq_backbone`) — float32 stream,
coefficients and Sinkhorn chain; φ's product has operands in the matmul
dtype like every product ``_mm`` makes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, ClassVar, Dict, Optional, Tuple

import numpy as np

from predictionio_tpu.models import glm4_moe_lite as glm
from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models.seq_backbone import (
    _cast_in_loop, _chunked_ce, _dt, _moe, _rms, _stacked,
    _swiglu, scope)
from predictionio_tpu.ops import hyper_connections as hc

#: the published ``rope_scaling`` block, as the config keeps it (sorted
#: pairs: the config is hashed)
_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 64), ("mscale", 1),
         ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096),
         ("type", "yarn"))
#: where the coefficients start (the config has no key; :func:`_init_leaf`):
#: α₁ = α₂ = α₃; mat(b[2n:]) off the diagonal (0 on it); b's spread
ALPHA_INIT = 0.01
RES_OFF_INIT = -3.0
B_STD = 0.5


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim: int, theta: float, factor: float, beta_fast: float,
               beta_slow: float, original: int) -> Tuple[float, ...]:
    """The ``dim``/2 frequencies of a YaRN rope (DeepSeek-V3's): a
    frequency that turns more than ``beta_fast`` times inside
    ``original`` positions stays θ^(−2i/dim), one that turns fewer than
    ``beta_slow`` times is divided by ``factor``, a linear ramp over
    the dims between."""
    def dim_of(turns: float) -> float:
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return tuple(float(f) for f in plain * (1 - ramp) + plain / factor * ramp)


@dataclass(frozen=True)
class XingConfig(glm.GlmConfig):
    model_type: ClassVar[str] = "xing4_0"
    #: GLM's, but a rope scaling (checked below) and this family's keys
    _REQUIRED: ClassVar[Dict[str, Any]] = {
        **{k: v for k, v in glm.GlmConfig._REQUIRED.items()
           if k != "rope_scaling"},
        "scoring_func": "sigmoid", "moe_layer_freq": 1,
        "model_type": "xing4_0"}
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    first_k_dense_replace: int = 2
    num_hidden_layers: int = 40
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e4
    rope_scaling: tuple = _YARN
    vocab_size: int = 131072
    # -- the residual stream ---------------------------------------------
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    seqs_per_step: int = 1

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]) -> "XingConfig":
        arch = dict(arch)
        scaling = arch.get("rope_scaling", dict(_YARN))
        if not isinstance(scaling, dict):
            scaling = dict(scaling or ())
        if (scaling.get("type") != "yarn"
                or set(scaling) != {k for k, _ in _YARN}):
            raise ValueError(f"architecture.rope_scaling = {scaling!r}: "
                             f"a yarn block with the keys of "
                             f"{dict(_YARN)!r} is implemented")
        if (yarn_mscale(scaling["factor"], scaling["mscale"])
                != yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])):
            raise ValueError("rope_scaling: only mscale == mscale_all_dim "
                             "(cos and sin unscaled) is implemented")
        arch["rope_scaling"] = tuple(sorted(scaling.items()))
        # the table's checks, not GLM's (which holds GLM to ONE module)
        c = super(glm.GlmConfig, cls).from_architecture(arch)
        if arch.get("num_key_value_heads",
                    c.num_attention_heads) != c.num_attention_heads:
            raise ValueError("latent attention has one key per head")
        if c.num_nextn_predict_layers not in (0, 1):
            raise ValueError("0 or 1 MTP modules are implemented")
        if c.hc_mult < 2:
            raise ValueError("hc_mult >= 2: a stream of one copy is the "
                             "glm4_moe_lite block")
        return c

    @property
    def yarn(self) -> Dict[str, Any]:
        return dict(self.rope_scaling)

    @property
    def rope_freqs(self) -> Tuple[float, ...]:
        y = self.yarn
        return yarn_freqs(self.qk_rope_head_dim, self.rope_theta,
                          y["factor"], y["beta_fast"], y["beta_slow"],
                          y["original_max_position_embeddings"])

    @property
    def softmax_scale(self) -> float:
        y = self.yarn
        return (yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
                / math.sqrt(self.qk_head_dim))

    @property
    def hc_width(self) -> int:
        """2n + n²: a token's coefficients a sublayer."""
        return self.hc_mult * (2 + self.hc_mult)

    @property
    def heads(self) -> Tuple[str, ...]:
        return ("loss", "mtp_loss")[:1 + self.num_nextn_predict_layers]


# -- parameters ---------------------------------------------------------------


def _hc_shapes(c: XingConfig) -> Dict[str, tuple]:
    """One sublayer's hyper-connection: φ as [n, d, 2n + n²] (row
    i·d + k of the paper's matrix is ``phi[i, k]``), b, α."""
    return {"phi": (c.hc_mult, c.hidden_size, c.hc_width),
            "b": (c.hc_width,), "alpha": (3,)}


def _block_shapes(c: XingConfig, dense: bool) -> Dict[str, Any]:
    return dict(glm._block_shapes(c, dense), hc_attn=_hc_shapes(c),
                hc_ffn=_hc_shapes(c))


def param_shapes(c: XingConfig) -> Dict[str, Any]:
    """GLM's tree with a hyper-connection a sublayer; the ``moe`` stack
    holds the MTP module's block (its last slice) and ``mtp`` exists
    only where the config has the module."""
    m = c.num_nextn_predict_layers
    out = dict(
        glm.param_shapes(c),
        dense=_stacked(_block_shapes(c, True), c.first_k_dense_replace),
        moe=_stacked(_block_shapes(c, False), c.n_moe_layers + m))
    if not m:
        del out["mtp"]
    return out


def _init_leaf(name: str, key, shape):
    """Where a hyper-connection's b and α start (φ: the rule's normal):
    α = ``ALPHA_INIT`` thrice; b = normal(b₀, ``B_STD``) around the b₀
    at which H_pre = σ(−ln(n − 1)) = 1/n, H_post = 2·σ(0) = 1 and
    H_res is the Sinkhorn chain of exp of 0 on and ``RES_OFF_INIT`` off
    the diagonal (n = 4: 0.87 on it, 0.043 beside). The spread is what
    makes the copies DIFFER from the first write-back on: at b₀ itself
    every copy is written alike and the stream is one array n times,
    whatever mixes it."""
    import jax
    import jax.numpy as jnp

    if name.endswith(".alpha"):
        return jnp.full(shape, ALPHA_INIT, jnp.float32)
    if not name.endswith((".hc_attn.b", ".hc_ffn.b")):
        return None
    n = math.isqrt(shape[-1] + 1) - 1            # 2n + n² = (n + 1)² − 1
    res = np.where(np.eye(n, dtype=bool), 0.0, RES_OFF_INIT).reshape(-1)
    start = np.concatenate([np.full(n, -math.log(n - 1)), np.zeros(n), res])
    return (jnp.asarray(start, jnp.float32)
            + B_STD * jax.random.normal(key, shape, jnp.float32))


def group_squares(grads) -> Dict[str, Any]:
    """Σ g² per parameter group (GLM's groups and ``dense.hc_attn`` …
    ``moe.hc_ffn``); with the MTP module, the ``moe`` stack's last slice
    goes under ``mtp.*`` as in GLM."""
    if "mtp" in grads:
        return glm.group_squares(grads)
    return seq_backbone.squares_by_group(grads, glm.group_of)


# -- the block ----------------------------------------------------------------


def _hyper(w, X, F, c: XingConfig):
    """One sublayer ``F`` ([T, d] → [T, d]) around the stream X
    [n, T, d]: (the stream after it, F's other results, how far H_res
    is from doubly stochastic)."""
    import jax

    with scope("seqrec.mhc.coef"):
        pre, post, res = hc.coefficients(
            X, w["phi"], w["b"], w["alpha"], norm_eps=c.rms_norm_eps,
            iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
            clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
            dtype=_dt(c))
        err = jax.lax.stop_gradient(hc.ds_error(res))
    with scope("seqrec.mhc.mix"):
        u = hc.read(X, pre)
    y, aux = F(u)
    with scope("seqrec.mhc.mix"):
        return hc.write(X, res, post, y), aux, err


def _block(w, X, seg, pos, bias, c: XingConfig):
    """One layer on the stream X [n, B, S, d] float32; ``bias`` None
    marks a dense layer. → (X, the routing's records or None, the
    layer's larger ``mhc_ds_err``)."""
    import jax.numpy as jnp

    n, B, S, d = X.shape
    eps = c.rms_norm_eps

    def attention(u):
        with scope("seqrec.mla"):
            return glm._mla(w["attn"], _rms(u.reshape(B, S, d),
                                            w["attn_norm"], eps),
                            seg, pos, c).reshape(B * S, d), None

    def ffn(u):
        with scope("seqrec.norm"):
            h = _rms(u, w["ffn_norm"], eps)
        if bias is None:
            with scope("seqrec.ffn"):
                return _swiglu(w["ffn"], h, c), None
        return _moe(w, h, seg.reshape(-1) > 0, bias, c)

    X = X.reshape(n, B * S, d)
    X, _, e1 = _hyper(w["hc_attn"], X, attention, c)
    X, stats, e2 = _hyper(w["hc_ffn"], X, ffn, c)
    return X.reshape(n, B, S, d), stats, jnp.maximum(e1, e2)


def _stack(params, bias, batch, c: XingConfig, mtp: Optional[bool] = None):
    """Embedding, the n copies, the main stack and (``mtp``; default:
    where the config has the module) the MTP module: x_L and the
    module's output [B, S, d] — each the fold of its stream —, the
    expert layers' routing records (leading axis: layer, the module's
    block last) and ``mhc_ds_err`` over all layers."""
    import jax
    import jax.numpy as jnp

    mtp = bool(c.num_nextn_predict_layers) if mtp is None else mtp
    tokens, seg, pos = batch["tokens"], batch["seg"], batch["pos"]
    with scope("seqrec.embed"):
        x = params["embed"][tokens]
        nxt = params["embed"][batch["tgt1"]] if mtp else None
    n, copies = c.n_moe_layers, c.hc_mult

    def dense(X, w):        # → (the stream, the layer's mhc_ds_err)
        return jax.checkpoint(
            lambda w, X: _block(w, X, seg, pos, None, c)[::2])(w, X)

    def enter_mtp(X):
        with scope("seqrec.mhc"):
            x = hc.fold(X)
        h = glm._mtp_entry(params["mtp"], nxt, x, c)
        with scope("seqrec.mhc"):
            return hc.copies(h, copies), x

    def turn(i, w, b, X, x_last):
        w = _cast_in_loop(w, c, i)
        if mtp:
            X, x_last = jax.lax.cond(i == n, enter_mtp,
                                     lambda X: (X, x_last), X)
        X, stats, err = _block(w, X, seg, pos, b, c)
        return (X, x_last), (stats, err)

    def sparse(carry, iwb):
        return jax.checkpoint(turn)(*iwb, *carry)

    turns = n + 1 if mtp else n
    with scope("seqrec.stack"):
        with scope("seqrec.mhc"):
            X = hc.copies(x, copies)
        X, err_dense = jax.lax.scan(dense, X, params["dense"])
        # x_L rides beside the stream only where the module needs it
        (X, x_last), (stats, err) = jax.lax.scan(
            sparse, (X, x if mtp else None),
            (jnp.arange(turns),
             jax.tree.map(lambda a: a[:turns], params["moe"]),
             bias[:turns]))
        with scope("seqrec.mhc"):
            folded = hc.fold(X)
    worst = jnp.maximum(err.max(), err_dense.max(initial=0.0))
    return ((x_last, folded, stats, worst) if mtp
            else (folded, None, stats, worst))


def loss_fn(params, bias, batch, c: XingConfig):
    """CE(next item) [+ λ·CE_MTP(item after next)] and the step's
    records; ``batch``: tokens, seg, pos, tgt1, tgt2 [B, S] int32."""
    import jax.numpy as jnp

    x, xm, stats, err = _stack(params, bias, batch, c)

    def ce(norm, x, targets):
        return _chunked_ce(
            lambda x: glm._head_logits(params, norm, x, c), x, targets,
            c) / jnp.maximum((targets > 0).sum(), 1)

    ce1 = ce(params["final_norm"], x, batch["tgt1"])
    rec = {"loss": ce1, "moe": stats, "mhc_ds_err": err}
    if xm is None:
        return ce1, rec
    ce2 = ce(params["mtp"]["final_norm"], xm, batch["tgt2"])
    return ce1 + c.mtp_loss_weight * ce2, dict(rec, mtp_loss=ce2)


def logits_heads(params, bias, batch, c: XingConfig):
    """Each head's float32 logits [B, S, V] for whole sequences: the
    next item's, and the MTP module's where the config has it."""
    x, xm, _, _ = _stack(params, bias, batch, c)
    out = (glm._head_logits(params, params["final_norm"], x, c),)
    if xm is not None:
        out += (glm._head_logits(params, params["mtp"]["final_norm"], xm,
                                 c),)
    return out


def _next_logits(params, bias, batch, n, c: XingConfig):
    x, _, _, _ = _stack(params, bias, batch, c, mtp=False)
    return glm._head_logits(params, params["final_norm"], x[0, n - 1], c)


def fit_attrs(c: XingConfig) -> Dict[str, int]:
    """On ``seqrec.fit``: the copies of the stream, the sublayers a
    step mixes around, and the bytes of the n-copy boundaries a step
    keeps (one a turn: the stream that enters it)."""
    layers = c.num_hidden_layers + c.num_nextn_predict_layers
    return {"mhc_streams": c.hc_mult, "mhc_sublayers": 2 * layers,
            "mhc_kept_bytes": (layers * c.hc_mult * c.seqs_per_step
                               * c.seq_len * c.hidden_size * 4)}


# -- the declaration ----------------------------------------------------------


BACKBONE = seq_backbone.build(
    XingConfig, param_shapes=param_shapes,
    # the expert layers, then the MTP module's block where there is one
    bias_shape=lambda c: (c.n_moe_layers + c.num_nextn_predict_layers,
                          c.router_experts),
    group_squares=group_squares, loss_fn=loss_fn, logits=logits_heads,
    next_logits=_next_logits,
    # the heads a config MAY train; ``c.heads``: those it does
    heads=("loss", "mtp_loss"), batch_keys=glm.BATCH_KEYS,
    fit_attrs=fit_attrs, init_leaf=_init_leaf)
