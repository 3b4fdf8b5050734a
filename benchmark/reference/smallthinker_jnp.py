"""Plain reference of the ``smallthinker`` block stack the
``sequentialrec`` template trains (SmallThinker-21BA3B-Instruct's
``config.json``): forward, loss and — as ``jax.grad`` of this forward —
gradients, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort and no
dispatch (every held expert is applied to every token and masked by
its gate), no tile walk: attention is a dense masked softmax over ALL
the keys, the window a term of the mask, computed over BLOCKS of query
rows so that a 16,384-slot sequence fits (28 heads × 16,384² × 4 B
would be 30 GB). The weights are DATA: the tree the program trains
(``embed``, ``runs`` — a list of runs of identical layers with a
leading layer axis —, ``final_norm``, ``head``), handed over as arrays;
a run's kind (global | window) is read off ``sliding_window_layout`` at
the run's first layer.

The equations (h: residual stream; layer l is ``window`` where
``sliding_window_layout[l]`` = ``rope_layout[l]`` = 1, else ``global``):

    logits = h W_r                       the router reads the layer's INPUT
    ids = top-k(logits); gate = softmax(logits[ids])
    a = RMSNorm(h; g1); q = a W_q, k = a W_k, v = a W_v   (no bias, no QK norm)
    window layer: q, k <- RoPE(theta, all D dims); global layer: none
    query i sees key j  <=>  same segment, j <= i, (global or i - j < W)
    scores q.k / sqrt(D); head h reads key-value head h // (H // Hkv)
    h' = h + o W_o
    m = RMSNorm(h'; g2); h'' = h' + sum_e gate_e W_d^e(relu(W_g^e m) * W_u^e m)

then the final RMSNorm and the UNTIED head.

Departures from the published description, each also in the program:

1. The item catalog stands where the token vocabulary stood; id 0 is
   PAD. Histories are packed: attention is causal AND inside one
   segment, RoPE positions restart with each segment, the window counts
   rows (which inside a segment are positions), targets never cross a
   segment's end.
2. RoPE rotates halves ([a ; b] -> [a cos - b sin ; b cos + a sin]).
3. ``held`` lists the experts THIS chip holds (None = all): the router
   keeps its width and its top-k, only the held experts' part of the
   result is added, and that partial result goes on to the next layer.
4. ``described_as``'s "primary + secondary experts" has no key in the
   published ``config``: one level of experts.
5. No auxiliary balance loss and no router bias (``bias`` is taken and
   ignored: the train step of every backbone carries one).
6. For the on-chip check's compile time, a run's identical layers go
   by ``lax.scan`` over their stacked weights, attention's row blocks
   by ``lax.map`` (each block recomputed in the backward pass, or the
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


def attention(q, k, v, seg, scale, window=None):
    """q, k, v [S, H, D], seg [S] → [S, H, D]; dense masked softmax,
    ``ROW_BLOCK`` query rows at a time; ``window``: a row sees the keys
    fewer than ``window`` rows behind it."""
    S = q.shape[0]
    nb = max(S // ROW_BLOCK, 1)
    rb = S // nb
    keys = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, segb, row0 = args
        r = row0 + jnp.arange(rb)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        mask = ((segb[:, None] == seg[None, :]) & (segb[:, None] > 0)
                & (r[:, None] >= keys[None, :]))
        if window is not None:
            mask = mask & (r[:, None] - keys[None, :] < window)
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(rows, (q.reshape((nb, rb) + q.shape[1:]),
                             seg.reshape(nb, rb), jnp.arange(nb) * rb))
    return out.reshape((S,) + out.shape[2:])


def attend(w, x, seg, pos, cfg, windowed):
    """x [S, d] (normed) → [S, d]."""
    S = x.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = (x @ w["wq"]).reshape(S, H, -1)
    k = (x @ w["wk"]).reshape(S, Hkv, -1)
    v = (x @ w["wv"]).reshape(S, Hkv, -1)
    if windowed:
        q = rope(q, pos[:, None], cfg["rope_theta"])
        k = rope(k, pos[:, None], cfg["rope_theta"])
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    o = attention(q, k, v, seg, float(1.0 / np.sqrt(q.shape[-1])),
                  cfg["sliding_window_size"] if windowed else None)
    return o.reshape(S, -1) @ w["wo"]


def route(router, x, valid, k):
    """x [S, d] → (gate over ALL the router's experts [S, E], zero
    where not selected or a padding row; the router's load [E])."""
    logits = x @ router
    picked, ids = jax.lax.top_k(logits, k)
    chosen = jax.nn.one_hot(ids, router.shape[1], dtype=logits.dtype)
    gate = (chosen * jax.nn.softmax(picked, axis=-1)[..., None]).sum(1)
    load = (chosen.sum(1) * valid[:, None]).sum(0)
    return gate * valid[:, None], load.astype(jnp.float32)


def experts(ex, m, gate, held):
    """Every held expert's ReGLU on every token, weighted by its gate:
    m [S, d] (normed), gate [S, E] → this share's part [S, d]."""
    held = jnp.asarray(list(range(gate.shape[1])) if held is None
                       else list(held))
    out = jnp.einsum(
        "esf,efd->esd",
        jax.nn.relu(jnp.einsum("sd,edf->esf", m, ex["wg"]))
        * jnp.einsum("sd,edf->esf", m, ex["wu"]), ex["wd"])
    return jnp.einsum("esd,se->sd", out, gate[:, held])


def layer(w, h, seg, pos, held, cfg, windowed):
    """One layer on h [S, d] → (h'', the router's load)."""
    eps = cfg["rms_norm_eps"]
    gate, load = route(w["router"], h, (seg > 0).astype(h.dtype),
                       cfg["moe_num_active_primary_experts"])
    h = h + attend(w["attn"], rms_norm(h, w["attn_norm"], eps), seg, pos,
                   cfg, windowed)
    m = rms_norm(h, w["ffn_norm"], eps)
    return h + experts(w["experts"], m, gate, held), load


def forward(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
            dtype=jnp.float32):
    """ONE packed sequence (``seq``: tokens, seg, pos [S] int32) →
    (logits [S, V] float32, loads [layers, E]). ``bias`` is ignored."""
    del bias
    w = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), weights)
    seg, pos = seq["seg"], seq["pos"]
    x = w["embed"][seq["tokens"]]
    loads, at = [], 0
    for run in w["runs"]:
        windowed = bool(cfg["sliding_window_layout"][at])
        x, load = jax.lax.scan(
            lambda x, wl, windowed=windowed: wrap(lambda wl, x: layer(
                wl, x, seg, pos, held, cfg, windowed))(wl, x), x, run)
        loads.append(load)
        at += run["attn_norm"].shape[0]
    logits = rms_norm(x, w["final_norm"], cfg["rms_norm_eps"]) @ w["head"]
    return logits.astype(jnp.float32), jnp.concatenate(loads)


def ce_sum(logits, targets):
    """Σ cross-entropy over the real targets (0 = none), float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.where(targets > 0, lse - hit, 0.0).sum()


def loss(weights, bias, batch, cfg, held=None, wrap=lambda f: f,
         dtype=jnp.float32):
    """A step's loss over ``batch`` ([B, S] per key): the mean
    cross-entropy over the batch's real targets; also the loads summed
    over the batch. One sequence at a time."""
    def one(seq):
        logits, loads = forward(weights, bias, seq, cfg, held, wrap, dtype)
        return ce_sum(logits, seq["tgt1"]), loads

    ce, loads = jax.lax.map(wrap(one), batch)
    return (ce.sum() / jnp.maximum((batch["tgt1"] > 0).sum(), 1),
            loads.sum(0))


def loss_and_grads(weights, bias, batch, cfg, held=None, wrap=lambda f: f):
    """((loss, loads), gradients of every weight), under ``highest``
    matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(
            weights, bias, batch, cfg, held, wrap)
