"""Plain reference of the ``xing4_0`` block stack the ``sequentialrec``
template trains (Xing4.0-29B-A4B's ``config.json``: DeepSeek-V3's latent
attention with YaRN positions, sigmoid router, shared expert and MTP
module, around every sublayer a residual stream of ``hc_mult`` copies
mixed by manifold-constrained hyper-connections, arXiv:2512.24880 after
arXiv:2409.19606): forward, loss and — as ``jax.grad`` of this forward —
gradients, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort and no
dispatch (every held expert is applied to every token and masked by its
gate), no recomputation, attention a dense masked softmax (in row
blocks, so that 4,096 positions fit), the stream a token's ``[n, d]``
matrix, its coefficients ``[S, n]`` and ``[S, n, n]`` arrays and the
Sinkhorn chain written as its equations. The weights are DATA: the tree
the program trains (``models/xing4_0.param_shapes``), handed over as
arrays.

Per token, X ∈ R^{n×d}; around each sublayer F with its φ, b, α:

    x̂ = vec(X) / rms(vec(X));  m = x̂ φ
    H_pre = σ(α₁·m[:n] + b[:n]);  H_post = 2·σ(α₂·m[n:2n] + b[n:2n])
    A = clamp(α₃·mat(m[2n:]) + mat(b[2n:]));  M = exp(A)
    hc_sinkhorn_iters ×: rows ÷ (sum + hc_eps), columns ÷ (sum + hc_eps)
    u = Σᵢ H_pre[i]·X[i];  y = F(RMSNorm_w(u))
    X[i] ← Σⱼ H_res[i, j]·X[j] + H_post[i]·y

Departures from the published description, each also in the program:

1. The item catalog stands where the token vocabulary stood; id 0 is
   PAD. Histories are packed: attention is causal AND inside one
   segment, RoPE positions restart with each segment, targets never
   cross a segment's end.
2. RoPE rotates halves ([a ; b] → [a cos − b sin ; b cos + a sin]);
   the published code interleaves pairs. With seeded random weights
   the two differ by a fixed permutation of the rope dims. YaRN as
   DeepSeek-V3 writes it: the frequencies' blend by a linear ramp
   between the dims of ``beta_fast`` and ``beta_slow`` rotations, the
   softmax scale × (0.1·mscale_all_dim·ln factor + 1)²; cos and sin
   unscaled (mscale = mscale_all_dim).
3. ``held`` lists the experts THIS chip holds (None = all): the router
   keeps its width and its top-k, only the held experts' part of the
   result (plus the shared expert) is added, and that partial result
   goes on to the next layer.
4. What the config has no key for: X₀ is n copies of the embedding and
   the stream folds by a SUM (the hyper-connections paper's); the norm
   before φ has no gain; the clamp comes before exp; rows are divided
   before columns, ``hc_eps`` added to each sum. φ is stored
   ``[n, d, 2n + n²]``: row i·d + k of the paper's matrix is
   ``phi[i, k]``.
5. MTP (where the weights hold the module): h'_i = W_eh
   [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L,i)], x_L the FOLD of the main
   stream; h' enters the module's block as n copies and its output
   folds by the same sum. Loss = CE + λ·CE_MTP, each a mean over its
   real targets. Without the module there is one head.
6. For the on-chip check's compile time, loops are ``lax`` loops over
   stacked data where a Python loop would do the same (the identical
   layers, the MTP module's block as the ``moe`` stack's last slice,
   attention's row blocks, the held experts as one batched product);
   ``wrap`` (default: nothing) lets that check wrap each layer in
   ``jax.checkpoint``; the CPU tests run unwrapped. Attention's row
   block is ALWAYS a ``jax.checkpoint``: the backward pass makes a
   block's scores and probabilities again (the same numbers) instead
   of keeping all 32 heads' 4,096 × 4,096 of a sequence, 2.1 GB — with
   them the check's program passed the chip's memory by 0.15 GB beside
   3 × 3.04 GB of weights, gradients and their accumulator.
7. ``dtype`` (default float32) computes EVERYTHING in a lower
   precision, and ``FAULT`` plants one wrong equation — what the
   comparison must catch (``xing4_precision_probe.py``); neither is
   the reference.

Nothing here imports the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512
#: the probe's switch, read when a forward is traced: None, or one of
#: "res_transposed" (H_res used as its transpose), "post_unscaled"
#: (H_post without its factor 2), "plain_scale" (the softmax scale
#: without mscale²)
FAULT = None


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(dim, theta, y):
    """The dim/2 frequencies: θ^(−2i/dim) where a frequency turns more
    than ``beta_fast`` times in the original positions, that ÷ factor
    where it turns fewer than ``beta_slow`` times, a linear ramp
    between."""
    def correction_dim(turns):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope(x, pos, freq):
    half = x.shape[-1] // 2
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(freq)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v, seg, scale):
    """q, k [S, H, D], v [S, H, Dv], seg [S] → [S, H, Dv]; dense
    masked softmax, ``ROW_BLOCK`` query rows at a time (a block's
    probabilities are made again in the backward pass: departure 6)."""
    S = q.shape[0]
    nb = max(S // ROW_BLOCK, 1)
    rb = S // nb

    @jax.checkpoint
    def rows(args):
        qb, segb, row0 = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        mask = ((segb[:, None] == seg[None, :]) & (segb[:, None] > 0)
                & ((row0 + jnp.arange(rb))[:, None]
                   >= jnp.arange(S)[None, :]))
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(rows, (q.reshape((nb, rb) + q.shape[1:]),
                             seg.reshape(nb, rb), jnp.arange(nb) * rb))
    return out.reshape((S,) + out.shape[2:])


def mla(w, x, seg, pos, cfg):
    """x [S, d] (normed) → [S, d]."""
    S = x.shape[0]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r, eps, y = cfg["kv_lora_rank"], cfg["rms_norm_eps"], dict(
        cfg["rope_scaling"])
    freq = yarn_freqs(dr, cfg["rope_theta"], y)
    scale = 1.0 / math.sqrt(dn + dr)
    if FAULT != "plain_scale":
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    q = (rms_norm(x @ w["wqa"], w["q_norm"], eps) @ w["wqb"]).reshape(
        S, H, dn + dr)
    ckv = x @ w["wkva"]
    k_r = rope(ckv[:, r:], pos, freq)
    kv = (rms_norm(ckv[:, :r], w["kv_norm"], eps) @ w["wkvb"]).reshape(
        S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos[:, None], freq)],
                        -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (S, H, dr))], -1)
    o = attention(q, k, kv[..., dn:], seg, scale)
    return o.reshape(S, H * dv) @ w["wo"]


def swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def moe(w, x, valid, bias, held, cfg):
    """x [S, d] (normed) → (this share's part of the result, the
    router's load over ALL its experts). ``bias`` enters the selection
    only."""
    E, k = w["router"].shape[1], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["router"])
    _, ids = jax.lax.top_k(s + bias[None, :].astype(s.dtype), k)
    picked = jnp.take_along_axis(s, ids, axis=1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(1, keepdims=True) + 1e-20)
    chosen = jax.nn.one_hot(ids, E, dtype=s.dtype)            # [S, k, E]
    gate = (chosen * (picked * cfg["routed_scaling_factor"])[..., None]
            ).sum(1) * valid[:, None]                         # [S, E]
    held = jnp.asarray(list(range(E)) if held is None else list(held))
    ex = w["experts"]               # every held expert on every token
    out = jnp.einsum(
        "esf,efd->esd",
        jax.nn.silu(jnp.einsum("sd,edf->esf", x, ex["wg"]))
        * jnp.einsum("sd,edf->esf", x, ex["wu"]), ex["wd"])
    y = swiglu(w["shared"], x) + jnp.einsum("esd,se->sd", out,
                                             gate[:, held])
    load = (chosen.sum(1) * valid[:, None]).sum(0)
    return y, load.astype(jnp.float32)


def sinkhorn(M, iters, eps):
    """M [S, n, n] positive: ``iters`` times every row ÷ (its sum +
    eps), then every column ÷ (its sum + eps)."""
    for _ in range(iters):
        M = M / (M.sum(-1, keepdims=True) + eps)
        M = M / (M.sum(-2, keepdims=True) + eps)
    return M


def coefficients(w, X, cfg):
    """X [S, n, d] → (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    S, n, d = X.shape
    flat = X.reshape(S, n * d)
    x_hat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                 + cfg["rms_norm_eps"])
    m = x_hat @ w["phi"].reshape(n * d, -1)
    a, b = w["alpha"], w["b"]
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    if FAULT != "post_unscaled":
        post = 2.0 * post
    A = jnp.clip(a[2] * m[:, 2 * n:] + b[2 * n:],
                 cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    res = sinkhorn(jnp.exp(A).reshape(S, n, n), cfg["hc_sinkhorn_iters"],
                   cfg["hc_eps"])
    if FAULT == "res_transposed":
        res = res.transpose(0, 2, 1)
    return pre, post, res


def hyper(w, X, F, cfg):
    """One sublayer F ([S, d] → [S, d]) around the stream X [S, n, d]."""
    pre, post, res = coefficients(w, X, cfg)
    y = F(jnp.einsum("si,sid->sd", pre, X))
    return jnp.einsum("sij,sjd->sid", res, X) + post[:, :, None] * y[:, None]


def block(w, X, seg, pos, bias, held, cfg):
    """One layer on the stream X [S, n, d]; ``bias`` None marks a dense
    layer. → (X, the router's load or None)."""
    eps, load = cfg["rms_norm_eps"], []
    X = hyper(w["hc_attn"], X, lambda u: mla(
        w["attn"], rms_norm(u, w["attn_norm"], eps), seg, pos, cfg), cfg)

    def ffn(u):
        h = rms_norm(u, w["ffn_norm"], eps)
        if bias is None:
            return swiglu(w["ffn"], h)
        y, ld = moe(w, h, (seg > 0).astype(h.dtype), bias, held, cfg)
        load.append(ld)
        return y

    X = hyper(w["hc_ffn"], X, ffn, cfg)
    return X, (load[0] if load else None)


def forward(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
            dtype=jnp.float32):
    """ONE packed sequence (``seq``: tokens, seg, pos, tgt1 [S] int32)
    → (logits [S, V], [MTP logits [S, V] where the weights hold the
    module,] loads [L (+ 1), E] — the module's block last), float32."""
    w = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), weights)
    seg, pos, eps = seq["seg"], seq["pos"], cfg["rms_norm_eps"]
    n_copies = cfg["hc_mult"]
    mtp = "mtp" in w
    x = w["embed"][seq["tokens"]]
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n_copies, x.shape[1]))
    n = w["moe"]["router"].shape[0] - int(mtp)

    def dense(X, wl):
        return wrap(lambda wl, X: block(wl, X, seg, pos, None, held,
                                        cfg)[0])(wl, X), None

    def enter_mtp(X):
        m, x = w["mtp"], X.sum(1)
        h = jnp.concatenate(
            [rms_norm(w["embed"][seq["tgt1"]], m["enorm"], eps),
             rms_norm(x, m["hnorm"], eps)], -1) @ m["eh_proj"]
        return jnp.broadcast_to(h[:, None, :], X.shape), x

    def sparse(carry, iwb):
        i, wl, b = iwb
        X, x_last = carry
        if mtp:
            X, x_last = jax.lax.cond(i == n, enter_mtp,
                                     lambda X: (X, x_last), X)
        X, load = wrap(lambda wl, b, X: block(wl, X, seg, pos, b, held,
                                              cfg))(wl, b, X)
        return (X, x_last), load

    X, _ = jax.lax.scan(dense, X, w["dense"])
    (X, x_last), loads = jax.lax.scan(
        sparse, (X, x), (jnp.arange(n + int(mtp)), w["moe"], bias))
    x = X.sum(1)
    if not mtp:
        return ((rms_norm(x, w["final_norm"], eps)
                 @ w["head"]).astype(jnp.float32), loads)
    logits = rms_norm(x_last, w["final_norm"], eps) @ w["head"]
    mtp_logits = rms_norm(x, w["mtp"]["final_norm"], eps) @ w["head"]
    return (logits.astype(jnp.float32), mtp_logits.astype(jnp.float32),
            loads)


def ce_sum(logits, targets):
    """Σ cross-entropy over the real targets (0 = none), float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.where(targets > 0, lse - hit, 0.0).sum()


def loss(weights, bias, batch, cfg, held=None, wrap=lambda f: f,
         dtype=jnp.float32):
    """A step's loss over ``batch`` ([B, S] per key): CE [+ λ·CE_MTP],
    each a mean over the batch's real targets; also ([CE of each
    head], loads summed over the batch). One sequence at a time."""
    targets = ("tgt1", "tgt2")

    def one(seq):
        *logits, loads = forward(weights, bias, seq, cfg, held, wrap, dtype)
        return [ce_sum(lg, seq[t]) for lg, t in zip(logits, targets)], loads

    sums, loads = jax.lax.map(wrap(one), batch)
    ces = [s.sum() / jnp.maximum((batch[t] > 0).sum(), 1)
           for s, t in zip(sums, targets)]
    total = ces[0] + (cfg["mtp_loss_weight"] * ces[1] if len(ces) > 1 else 0)
    return total, (ces, jax.tree.map(lambda a: a.sum(0), loads))


def loss_and_grads(weights, bias, batch, cfg, held=None, wrap=lambda f: f):
    """((loss, ([CE of each head], loads)), gradients of every weight),
    under ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            weights, bias, batch, cfg, held, wrap)


def bias_update(bias, load, rate):
    """b_e ← b_e + γ·sign(mean load − load_e), over all the router's
    experts; ``load`` [..., E]."""
    return bias + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
