"""Plain reference of the ``lfm2_moe`` hybrid block stack the
``sequentialrec`` template trains (LFM2-8B-A1B's ``config.json``):
forward, loss and — as ``jax.grad`` of this forward — gradients, in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no sort and no
dispatch (every held expert is applied to every token and masked by
its gate), no recomputation; attention a dense masked softmax (in row
blocks, so that 4,096 positions fit) with each key-value head repeated
for the query heads of its group; the convolution three shifted,
segment-masked products. The weights are DATA: the tree the program
trains (``embed``, ``runs`` — a list of runs of identical layers with
a leading layer axis —, ``final_norm``), handed over as arrays; a
run's kind is read off its keys (``conv`` | ``attn``, ``ffn`` |
``router`` + ``experts``).

The equations:

- layer: x ← x + Op(RMSNorm_op(x)); x ← x + FFN(RMSNorm_ffn(x));
- gated short convolution: [B ; C ; u] = x W_in; z = B ⊙ u;
  c_t = Σ_j w[j] ⊙ z_{t−(L−1)+j}; y = (C ⊙ c) W_out;
- attention: per-head RMSNorm on q and k, then RoPE, scores q·k/√D,
  query head h reads key-value head h ÷ (H ÷ Hkv);
- router: sigmoid scores, top-k of score + bias, gates s_e/Σ s ×
  scaling; no shared expert;
- head: RMSNorm_final(x_L) Eᵀ with E the embedding.

Departures from the published description, each also in the program:

1. The item catalog stands where the token vocabulary stood; id 0 is
   PAD. Histories are packed: attention is causal AND inside one
   segment, RoPE positions restart with each segment, targets never
   cross a segment's end.
2. A tap of the convolution that reaches before its segment's first
   row is zero (here: the row it would read lies in another segment),
   as the unpacked model's left padding makes it.
3. RoPE rotates halves ([a ; b] → [a cos − b sin ; b cos + a sin]).
4. ``held`` lists the experts THIS chip holds (None = all): the router
   keeps its width and its top-k, only the held experts' part of the
   result is added, and that partial result goes on to the next layer.
5. The gates' denominator is Σ_selected s + 1e-20.
6. For the on-chip check's compile time, a run's identical layers go
   by ``lax.scan`` over their stacked weights, attention's row blocks
   by ``lax.map``, the held experts by one batched product. ``wrap``
   (default: nothing) lets that check wrap each layer in
   ``jax.checkpoint`` so that the gradients of 508 M parameters fit
   beside the activations; the CPU tests run unwrapped.
7. ``dtype`` (default float32) computes EVERYTHING in a lower
   precision — what the comparison must catch; it is not the
   reference.

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
    """q, k, v [S, H, D], seg [S] → [S, H, D]; dense masked softmax,
    ``ROW_BLOCK`` query rows at a time."""
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


def gqa(w, x, seg, pos, cfg):
    """x [S, d] (normed) → [S, d]."""
    S = x.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = (x @ w["wq"]).reshape(S, H, -1)
    k = (x @ w["wk"]).reshape(S, Hkv, -1)
    v = (x @ w["wv"]).reshape(S, Hkv, -1)
    q = rope(rms_norm(q, w["q_norm"], eps), pos[:, None], theta)
    k = rope(rms_norm(k, w["k_norm"], eps), pos[:, None], theta)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    o = attention(q, k, v, seg, float(1.0 / np.sqrt(q.shape[-1])))
    return o.reshape(S, -1) @ w["wo"]


def short_conv(w, x, seg):
    """x [S, d] (normed) → [S, d]; ``w["taps"]`` [L, d], tap j reads
    the row L − 1 − j before, if that row is of the same segment."""
    S = x.shape[0]
    b, c, u = jnp.split(x @ w["w_in"], 3, axis=-1)
    z, taps = b * u, w["taps"]
    L = taps.shape[0]
    conv = jnp.zeros_like(z)
    for j in range(L):
        back = L - 1 - j
        z_before = jnp.concatenate([jnp.zeros_like(z[:back]), z[:S - back]])
        seg_before = jnp.concatenate(
            [jnp.full((back,), -1, seg.dtype), seg[:S - back]])
        # a padding row (segment 0) reads no row before it
        same = (seg_before == seg) & ((seg > 0) | (back == 0))
        conv = conv + taps[j] * z_before * same[:, None]
    return (c * conv) @ w["w_out"]


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
    y = jnp.einsum("esd,se->sd", out, gate[:, held])
    load = (chosen.sum(1) * valid[:, None]).sum(0)
    return y, load.astype(jnp.float32)


def layer(w, x, seg, pos, bias, held, cfg):
    """One layer on x [S, d]; its kind is what its weights hold."""
    eps = cfg["norm_eps"]
    h = rms_norm(x, w["op_norm"], eps)
    x = x + (short_conv(w["conv"], h, seg) if "conv" in w
             else gqa(w["attn"], h, seg, pos, cfg))
    h = rms_norm(x, w["ffn_norm"], eps)
    if "ffn" in w:
        return x + swiglu(w["ffn"], h), None
    y, load = moe(w, h, (seg > 0).astype(h.dtype), bias, held, cfg)
    return x + y, load


def forward(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
            dtype=jnp.float32):
    """ONE packed sequence (``seq``: tokens, seg, pos [S] int32) →
    (logits [S, V] float32, loads [expert layers, E])."""
    w = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), weights)
    seg, pos = seq["seg"], seq["pos"]
    x = w["embed"][seq["tokens"]]
    loads, at = [], 0
    for run in w["runs"]:
        n = run["op_norm"].shape[0]
        if "ffn" in run:
            x, _ = jax.lax.scan(
                lambda x, wl: (wrap(lambda wl, x: layer(
                    wl, x, seg, pos, None, held, cfg)[0])(wl, x), None),
                x, run)
            continue
        x, load = jax.lax.scan(
            lambda x, wb: wrap(lambda wl, b, x: layer(
                wl, x, seg, pos, b, held, cfg))(*wb, x),
            x, (run, bias[at:at + n]))
        loads.append(load)
        at += n
    logits = rms_norm(x, w["final_norm"], cfg["norm_eps"]) @ w["embed"].T
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


def bias_update(bias, load, rate):
    """b_e ← b_e + γ·sign(mean load − load_e), over all the router's
    experts; ``load`` [..., E]."""
    return bias + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
