"""Plain reference of the ``sdar_moe`` block stack the ``sequentialrec``
template trains by block diffusion (SDAR-30B-A3B-Chat's ``config.json``;
objective and mask of block diffusion, arXiv:2503.09573): forward, loss
and — as ``jax.grad`` of this forward — gradients, in straightforward
``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort and no
dispatch (every held expert is applied to every row and masked by its
gate), no tile walk and no second operand: the two streams of a
sequence are CONCATENATED (rows 0 … S − 1 the clean history, rows S …
2S − 1 the noised one) and attention is ONE dense masked softmax over
all 2·S keys, its mask built from the four visibility lines below,
computed over BLOCKS of query rows so that 2 × 8,192 rows fit (32 heads
× 16,384² × 4 B would be 34 GB). The weights are DATA: the tree the
program trains (``embed``, ``layers`` — the identical layers with a
leading layer axis —, ``final_norm``, ``head``), handed over as arrays.
The NOISE is data too: ``seq`` carries the noised stream's tokens
(``noised``) and every row's weight in the loss (``weight``: 1/p of its
block where the row is masked, else 0); nothing is drawn here.

The equations (h: residual stream of both streams; every layer alike):

    a = RMSNorm(h; g1); q = a W_q, k = a W_k, v = a W_v       (no bias)
    q <- RMSNorm over each head's D dims (g_q); k likewise (g_k); then
        RoPE(theta, all D dims), both streams at the SAME positions
    block(i) = position of i inside its segment // block_length; with
    i, j rows of the same segment:
        clean query i  sees clean key j   <=>  block(j) <= block(i)
        noised query i sees clean key j   <=>  block(j) <  block(i)
        noised query i sees noised key j  <=>  block(j) == block(i)
        no clean query sees a noised key
    scores q.k / sqrt(D); head h reads key-value head h // (H // Hkv)
    h' = h + o W_o
    m = RMSNorm(h'; g2); logits = m W_r; ids = top-k(logits)
    gate = softmax(logits)[ids] / sum_ids softmax(logits)
    h'' = h' + sum_e gate_e W_d^e(silu(W_g^e m) * W_u^e m)

then the final RMSNorm and the UNTIED head, on the noised stream's rows;

    loss = sum_i weight_i . CE(logits of the NOISED row i, tokens_i)
           / real events of the step          (no shift)

Departures from the published description, each also in the program:

1. The item catalog stands where the token vocabulary stood; id 0 is
   PAD, the vocabulary's last row MASK. Histories are packed: a row
   sees keys of its own segment only, RoPE positions and the blocks
   restart with each segment (so a segment's last block may be partial).
2. RoPE rotates halves ([a ; b] -> [a cos - b sin ; b cos + a sin]).
3. ``held`` lists the experts THIS chip holds (None = all): the router
   keeps its width and its top-k, only the held experts' part of the
   result is added, and that partial result goes on to the next layer.
4. Block length, the schedule p = eps + (1 - eps) u drawn per BLOCK and
   the weight 1/p are the training job's (the published config names
   none of them); the loss divides by the step's real events.
5. No auxiliary balance loss and no router bias (``bias`` is taken and
   ignored: the train step of every backbone carries one).
6. For the on-chip check's compile time, the identical layers go by
   ``lax.scan`` over their stacked weights, attention's row blocks by
   ``lax.map`` (each block recomputed in the backward pass, or the
   blocks' probabilities would all be kept), the held experts by one
   batched product. ``wrap`` (default: nothing) lets that check wrap
   each layer in ``jax.checkpoint`` so that the gradients fit beside
   the activations; the CPU tests run unwrapped.
7. ``dtype`` (default float32) computes EVERYTHING in a lower
   precision — what the comparison must catch; it is not the
   reference.

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 512


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def visible(seg_q, blk_q, noised_q, seg_k, blk_k, noised_k):
    """The four visibility lines: [queries, keys] bool from each row's
    segment, block inside it and stream."""
    same = (seg_q[:, None] == seg_k[None, :]) & (seg_q[:, None] > 0)
    bq, bk = blk_q[:, None], blk_k[None, :]
    nq, nk = noised_q[:, None], noised_k[None, :]
    return same & ((~nq & ~nk & (bk <= bq))      # clean sees clean
                   | (nq & ~nk & (bk < bq))      # noised sees clean
                   | (nq & nk & (bk == bq)))     # noised sees noised
    # and no clean query sees a noised key


def attention(q, k, v, seg, blk, scale):
    """q, k, v [2S, H, D] (clean rows, then noised), ``seg`` and ``blk``
    [S] (a slot's segment and its block inside it) → [2S, H, D]; one
    dense masked softmax over all 2·S keys, ``ROW_BLOCK`` query rows at
    a time."""
    R, S = q.shape[0], seg.shape[0]
    nb = max(R // ROW_BLOCK, 1)
    rb = R // nb
    seg2, blk2 = jnp.tile(seg, 2), jnp.tile(blk, 2)
    noised = jnp.arange(R) >= S

    @jax.checkpoint
    def rows(args):
        qb, segb, blkb, noisedb = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        mask = visible(segb, blkb, noisedb, seg2, blk2, noised)
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(rows, (q.reshape((nb, rb) + q.shape[1:]),
                             seg2.reshape(nb, rb), blk2.reshape(nb, rb),
                             noised.reshape(nb, rb)))
    return out.reshape((R,) + out.shape[2:])


def attend(w, x, seg, pos, cfg):
    """x [2S, d] (normed, both streams) → [2S, d]."""
    R = x.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, at = cfg["rms_norm_eps"], jnp.tile(pos, 2)[:, None]
    q = rope(rms_norm((x @ w["wq"]).reshape(R, H, -1), w["q_norm"], eps),
             at, cfg["rope_theta"])
    k = rope(rms_norm((x @ w["wk"]).reshape(R, Hkv, -1), w["k_norm"], eps),
             at, cfg["rope_theta"])
    v = (x @ w["wv"]).reshape(R, Hkv, -1)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    o = attention(q, k, v, seg, pos // cfg["block_length"],
                  float(1.0 / np.sqrt(q.shape[-1])))
    return o.reshape(R, -1) @ w["wo"]


def route(router, x, valid, k):
    """x [R, d] → (gate over ALL the router's experts [R, E], zero
    where not selected or a padding row; the router's load [E]): a
    softmax over all the experts, renormalised over the top-k."""
    prob = jax.nn.softmax(x @ router, axis=-1)
    picked, ids = jax.lax.top_k(prob, k)
    chosen = jax.nn.one_hot(ids, router.shape[1], dtype=prob.dtype)
    gate = (chosen * (picked / picked.sum(-1, keepdims=True))[..., None]
            ).sum(1)
    load = (chosen.sum(1) * valid[:, None]).sum(0)
    return gate * valid[:, None], load.astype(jnp.float32)


def experts(ex, m, gate, held):
    """Every held expert's SwiGLU on every row, weighted by its gate:
    m [R, d] (normed), gate [R, E] → this share's part [R, d]."""
    held = jnp.asarray(list(range(gate.shape[1])) if held is None
                       else list(held))
    out = jnp.einsum(
        "esf,efd->esd",
        jax.nn.silu(jnp.einsum("sd,edf->esf", m, ex["wg"]))
        * jnp.einsum("sd,edf->esf", m, ex["wu"]), ex["wd"])
    return jnp.einsum("esd,se->sd", out, gate[:, held])


def layer(w, h, seg, pos, held, cfg):
    """One layer on h [2S, d] → (h'', the router's load)."""
    eps = cfg["rms_norm_eps"]
    h = h + attend(w["attn"], rms_norm(h, w["attn_norm"], eps), seg, pos,
                   cfg)
    m = rms_norm(h, w["ffn_norm"], eps)
    gate, load = route(w["router"], m, jnp.tile(seg > 0, 2).astype(h.dtype),
                       cfg["num_experts_per_tok"])
    return h + experts(w["experts"], m, gate, held), load


def forward(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
            dtype=jnp.float32):
    """ONE packed sequence (``seq``: tokens, noised, seg, pos [S] int32)
    → (logits of the NOISED stream's rows [S, V] float32, loads
    [layers, E]). ``bias`` is ignored."""
    del bias
    w = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), weights)
    seg, pos = seq["seg"], seq["pos"]
    x = w["embed"][jnp.concatenate([seq["tokens"], seq["noised"]])]
    x, loads = jax.lax.scan(
        lambda x, wl: wrap(lambda wl, x: layer(wl, x, seg, pos, held, cfg))(
            wl, x), x, w["layers"])
    logits = rms_norm(x[seg.shape[0]:], w["final_norm"],
                      cfg["rms_norm_eps"]) @ w["head"]
    return logits.astype(jnp.float32), loads


def ce_sum(logits, targets, weights):
    """Σ weight · cross-entropy(logits, targets) over every row,
    float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return (weights * (lse - hit)).sum()


def loss(weights, bias, batch, cfg, held=None, wrap=lambda f: f,
         dtype=jnp.float32):
    """A step's loss over ``batch`` ([B, S] per key): Σ weight · CE of
    the noised rows against their own items, over the batch's real
    events; also the loads summed over the batch. One sequence at a
    time."""
    def one(seq):
        logits, loads = forward(weights, bias, seq, cfg, held, wrap, dtype)
        return ce_sum(logits, seq["tokens"], seq["weight"]), loads

    ce, loads = jax.lax.map(wrap(one), batch)
    return (ce.sum() / jnp.maximum((batch["seg"] > 0).sum(), 1),
            loads.sum(0))


def loss_and_grads(weights, bias, batch, cfg, held=None, wrap=lambda f: f):
    """((loss, loads), gradients of every weight), under ``highest``
    matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            weights, bias, batch, cfg, held, wrap)
