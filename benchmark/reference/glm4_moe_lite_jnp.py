"""Plain reference of the ``glm4_moe_lite`` block stack the
``sequentialrec`` template trains (GLM-4.7-Flash's ``config.json``;
DeepSeek-V3's MLA, router and MTP module): forward, loss and — as
``jax.grad`` of this forward — gradients, in straightforward
``jax.numpy`` and float32 under ``jax.default_matmul_precision(
"highest")``. No kernel, no sort and no dispatch (every held expert is
applied to every token and masked by its gate), no recomputation,
attention a dense masked softmax (in row blocks, so that 4,096
positions fit). The weights are DATA: the tree the program trains
(``models/glm4_moe_lite.param_shapes``), handed over as arrays.

Departures from the published description, each also in the program:

1. The item catalog stands where the token vocabulary stood; id 0 is
   PAD. Histories are packed: attention is causal AND inside one
   segment, RoPE positions restart with each segment, targets never
   cross a segment's end.
2. RoPE rotates halves ([a ; b] → [a cos − b sin ; b cos + a sin]);
   the published code interleaves pairs. With seeded random weights
   the two differ by a fixed permutation of the rope dims.
3. ``held`` lists the experts THIS chip holds (None = all): the router
   keeps its width and its top-k, only the held experts' part of the
   result (plus the shared expert) is added, and that partial result
   goes on to the next layer.
4. MTP: h'_i = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(x_L,i)] — the
   order of the halves is assumed; x_L is the stack's output before
   the final norm. Loss = CE + λ·CE_MTP, each a mean over its real
   targets.
5. For the on-chip check's compile time, loops are ``lax`` loops over
   stacked data where a Python loop would do the same: the identical
   layers by ``lax.scan`` over their stacked weights, the MTP module's
   block — the LAST slice of the ``moe`` stack, as the program keeps
   it — as that scan's last turn (a ``lax.cond`` swaps the stream for
   h' when the main stack has ended), attention's row blocks by
   ``lax.map``, the held experts by one batched product. ``wrap``
   (default: nothing) lets that check wrap each layer, and each
   sequence of a batch, in ``jax.checkpoint`` so that the gradients of
   706 M parameters fit beside the activations; the CPU tests run
   unwrapped.
6. ``dtype`` (default float32) computes EVERYTHING in a lower
   precision — what the comparison must catch (the cell's
   ``precision_probe``); it is not the reference.

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v, seg, scale):
    """q, k [S, H, D], v [S, H, Dv], seg [S] → [S, H, Dv]; dense
    masked softmax, ``ROW_BLOCK`` query rows at a time."""
    S = q.shape[0]
    nb = max(S // ROW_BLOCK, 1)
    rb = S // nb

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
    r, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], cfg["rope_theta"]
    q = (rms_norm(x @ w["wqa"], w["q_norm"], eps) @ w["wqb"]).reshape(
        S, H, dn + dr)
    ckv = x @ w["wkva"]
    k_r = rope(ckv[:, r:], pos, theta)
    kv = (rms_norm(ckv[:, :r], w["kv_norm"], eps) @ w["wkvb"]).reshape(
        S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos[:, None], theta)],
                        -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (S, H, dr))], -1)
    o = attention(q, k, kv[..., dn:], seg, float(1.0 / np.sqrt(dn + dr)))
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


def block(w, x, seg, pos, bias, held, cfg):
    """One layer on x [S, d]; ``bias`` None marks a dense layer."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(w["attn"], rms_norm(x, w["attn_norm"], eps), seg, pos, cfg)
    h = rms_norm(x, w["ffn_norm"], eps)
    if bias is None:
        return x + swiglu(w["ffn"], h), None
    y, load = moe(w, h, (seg > 0).astype(h.dtype), bias, held, cfg)
    return x + y, load


def forward(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
            dtype=jnp.float32):
    """ONE packed sequence (``seq``: tokens, seg, pos, tgt1 [S] int32)
    → (logits [S, V], MTP logits [S, V], loads [L + 1, E] — the MTP
    module's block last), all float32."""
    w = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), weights)
    seg, pos, eps = seq["seg"], seq["pos"], cfg["rms_norm_eps"]
    x = w["embed"][seq["tokens"]]
    m, nxt = w["mtp"], w["embed"][seq["tgt1"]]
    n = w["moe"]["router"].shape[0] - 1

    def dense(x, wl):
        return wrap(lambda wl, x: block(wl, x, seg, pos, None, held,
                                        cfg)[0])(wl, x), None

    def enter_mtp(x):
        return jnp.concatenate([rms_norm(nxt, m["enorm"], eps),
                                rms_norm(x, m["hnorm"], eps)],
                               -1) @ m["eh_proj"], x

    def sparse(carry, iwb):
        i, wl, b = iwb
        x, x_last = carry
        x, x_last = jax.lax.cond(i == n, enter_mtp, lambda x: (x, x_last), x)
        x, load = wrap(lambda wl, b, x: block(wl, x, seg, pos, b, held,
                                              cfg))(wl, b, x)
        return (x, x_last), load

    x, _ = jax.lax.scan(dense, x, w["dense"])
    (h, x), loads = jax.lax.scan(sparse, (x, x),
                                 (jnp.arange(n + 1), w["moe"], bias))
    logits = rms_norm(x, w["final_norm"], eps) @ w["head"]
    mtp_logits = rms_norm(h, m["final_norm"], eps) @ w["head"]
    return (logits.astype(jnp.float32), mtp_logits.astype(jnp.float32),
            loads)


def ce_sum(logits, targets):
    """Σ cross-entropy over the real targets (0 = none), float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.where(targets > 0, lse - hit, 0.0).sum()


def loss_sums(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
              dtype=jnp.float32):
    """(Σ CE next item, Σ CE item after next, loads) of one sequence."""
    logits, mtp_logits, loads = forward(weights, bias, seq, cfg, held, wrap,
                                        dtype)
    return (ce_sum(logits, seq["tgt1"]), ce_sum(mtp_logits, seq["tgt2"]),
            loads)


def loss(weights, bias, batch, cfg, held=None, wrap=lambda f: f,
         dtype=jnp.float32):
    """A step's loss over ``batch`` ([B, S] per key): CE + λ·CE_MTP,
    each a mean over the batch's real targets; also (CE, CE_MTP,
    loads summed over the batch). One sequence at a time."""
    def one(seq):
        return loss_sums(weights, bias, seq, cfg, held, wrap, dtype)

    ce1, ce2, loads = jax.lax.map(wrap(one), batch)
    n1 = jnp.maximum((batch["tgt1"] > 0).sum(), 1)
    n2 = jnp.maximum((batch["tgt2"] > 0).sum(), 1)
    ce1, ce2 = ce1.sum() / n1, ce2.sum() / n2
    return (ce1 + cfg["mtp_loss_weight"] * ce2,
            (ce1, ce2, jax.tree.map(lambda a: a.sum(0), loads)))


def loss_and_grads(weights, bias, batch, cfg, held=None, wrap=lambda f: f):
    """((loss, (CE, CE_MTP, loads)), gradients of every weight), under
    ``highest`` matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            weights, bias, batch, cfg, held, wrap)


def bias_update(bias, load, rate):
    """b_e ← b_e + γ·sign(mean load − load_e), over all the router's
    experts; ``load`` [..., E]."""
    return bias + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
