"""Plain reference of the ``qwen3_next`` hybrid block stack the
``sequentialrec`` template trains (Qwen3-Next-80B-A3B-Instruct's
``config.json``): forward, loss and — as ``jax.grad`` of this forward —
gradients, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no chunk, no
sort and no dispatch: the gated delta rule is the ROW-BY-ROW recurrence
(``lax.scan`` over the rows, checkpointed in blocks of ``SCAN_BLOCK``
rows so that its backward pass keeps a state a block and not a state a
row), attention a dense masked softmax over ALL the keys in blocks of
query rows, every held expert applied to every token and masked by its
gate (in blocks of rows, each recomputed in the backward pass: 32
experts × 16,384 rows × 2,048 would be 4 GB). The weights are DATA: the
tree the program trains (``embed``, ``runs`` — a list of runs of
identical layers with a leading layer axis —, ``final_norm``,
``head``), handed over as arrays; a run's kind is read off its keys
(``gdn`` | ``attn``).

The equations (x: residual stream; ``norm`` an RMS norm with gain
1 + w; layer i is full attention where (i + 1) % ``full_attention_interval``
= 0, else Gated DeltaNet):

    x <- x + mixer(norm(x; w1));  x <- x + moe(norm(x; w2))

    Gated DeltaNet (Hk key heads, Hv value heads, dk, dv):
      per key head [q | k | v v | z z] = x W_qkvz,  [b b | a a] = x W_ba
      [q, k, v] <- silu(conv4([q, k, v]))      depthwise, causal, no bias
      q <- q / |q| / sqrt(dk),  k <- k / |k|;  value head h reads key head h // (Hv // Hk)
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
      S <- e^g S;  delta = beta (v - S^T k);  S <- S + k delta^T;  o = S^T q
      o <- rmsnorm(o) * w_o * silu(z);  out = o W_out
    gated attention (H query heads over Hkv key-value heads of D):
      per head [q | gate] = x W_q;  k = x W_k;  v = x W_v
      q <- norm(q), k <- norm(k) over D;  RoPE on the first D * partial_rotary_factor dims
      causal softmax(q.k / sqrt(D)) v;  out = (attn * sigmoid(gate)) W_o
    experts:
      p = softmax(x W_r) over ALL experts; top-k; gates = p_e / sum_selected p
      y = sum_e gate_e W_d^e(silu(W_g^e x) * W_u^e x) + sigmoid(x w_s) * shared(x)

then the final norm and the UNTIED head.

Departures from the published description, each also in the program:

1. The item catalog stands where the token vocabulary stood; id 0 is
   PAD. Histories are packed: the recurrent state is ZERO at the first
   row of every segment (a maximal run of one segment id; the padding
   behind the histories is such a run), a tap of the convolution that
   reaches before its segment's first row is zero, attention is causal
   AND inside one segment, RoPE positions restart with each segment,
   targets never cross a segment's end.
2. RoPE rotates halves ([a ; b] -> [a cos - b sin ; b cos + a sin]).
3. |q| and |k| are sqrt(sum of squares + 1e-6): a padding row's are 0.
4. ``held`` lists the experts THIS chip holds (None = all): the router
   keeps its width and its top-k, only the held experts' part of the
   result is added (the shared expert whole), and that partial result
   goes on to the next layer.
5. No multi-token-prediction module, no auxiliary balance loss and no
   router bias (``bias`` is taken and ignored: the train step of every
   backbone carries one).
6. For the on-chip check's compile time, a run's identical layers go
   by ``lax.scan`` over their stacked weights, attention's and the
   experts' row blocks by ``lax.map``. ``wrap`` (default: nothing)
   lets that check wrap each layer in ``jax.checkpoint`` so that the
   gradients fit beside the activations; the CPU tests run unwrapped.
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
SCAN_BLOCK = 128


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks(n: int, most: int) -> int:
    """How many equal blocks of at most ``most`` rows ``n`` rows make."""
    nb = max(n // most, 1)
    while n % nb:
        nb += 1
    return nb


# -- the gated delta rule ------------------------------------------------------


def delta_rule(q, k, v, g, beta, seg):
    """The recurrence, one row at a time: q, k [S, H, dk], v [S, H, dv],
    g, beta [S, H], seg [S] → o [S, H, dv]. The state is zero at the
    first row of every run of one segment id."""
    S, H, dv = v.shape
    first = jnp.concatenate([jnp.ones(1, bool), seg[1:] != seg[:-1]])

    def row(state, x):
        q, k, v, g, beta, first = x
        state = jnp.where(first, jnp.zeros_like(state), state)
        state = jnp.exp(g)[:, None, None] * state
        delta = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(row, state, xs)

    nb = _blocks(S, SCAN_BLOCK)
    xs = jax.tree.map(lambda a: a.reshape((nb, S // nb) + a.shape[1:]),
                      (q, k, v, g, beta, first))
    _, out = jax.lax.scan(block, jnp.zeros((H, k.shape[-1], dv), v.dtype), xs)
    return out.reshape(S, H, dv)


def causal_conv(x, taps, seg):
    """Depthwise: x [S, …], ``taps`` [L, …]; tap j reads the row
    L − 1 − j before, if that row is of the same segment."""
    S, L = x.shape[0], taps.shape[0]
    out = jnp.zeros_like(x)
    for j in range(L):
        back = L - 1 - j
        before = jnp.concatenate([jnp.zeros_like(x[:back]), x[:S - back]])
        seg_before = jnp.concatenate(
            [jnp.full((back,), -1, seg.dtype), seg[:S - back]])
        # a padding row (segment 0) reads no row before it
        same = (seg_before == seg) & ((seg > 0) | (back == 0))
        out = out + taps[j] * before * same.reshape((S,) + (1,) * (x.ndim - 1))
    return out


def gated_delta_net(w, x, seg, cfg):
    """x [S, d] (normed) → [S, d]."""
    S = x.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = Hv // Hk
    qkvz = (x @ w["w_qkvz"]).reshape(S, Hk, 2 * dk + 2 * r * dv)
    ba = (x @ w["w_ba"]).reshape(S, Hk, 2 * r)
    z = qkvz[..., 2 * dk + r * dv:].reshape(S, Hv, dv)
    qkv = jax.nn.silu(causal_conv(
        qkvz[..., :2 * dk + r * dv],
        w["taps"].reshape(-1, Hk, 2 * dk + r * dv), seg))
    q = l2_norm(qkv[..., :dk]) * float(1.0 / np.sqrt(dk))
    k = l2_norm(qkv[..., dk:2 * dk])
    v = qkv[..., 2 * dk:].reshape(S, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(S, Hv))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(S, Hv) + w["dt_bias"])
    o = delta_rule(jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1), v, g,
                   beta, seg)
    o = rms_norm(o, w["out_norm"], cfg["rms_norm_eps"]) * jax.nn.silu(z)
    return o.reshape(S, Hv * dv) @ w["w_out"]


# -- gated attention -----------------------------------------------------------


def attention(q, k, v, seg, scale):
    """q, k, v [S, H, D], seg [S] → [S, H, D]; dense masked softmax,
    ``ROW_BLOCK`` query rows at a time."""
    S = q.shape[0]
    nb = _blocks(S, ROW_BLOCK)
    rb = S // nb
    keys = jnp.arange(S)

    @jax.checkpoint
    def rows(args):
        qb, segb, row0 = args
        r = row0 + jnp.arange(rb)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        mask = ((segb[:, None] == seg[None, :]) & (segb[:, None] > 0)
                & (r[:, None] >= keys[None, :]))
        p = jax.nn.softmax(jnp.where(mask[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(rows, (q.reshape((nb, rb) + q.shape[1:]),
                             seg.reshape(nb, rb), jnp.arange(nb) * rb))
    return out.reshape((S,) + out.shape[2:])


def gated_attention(w, x, seg, pos, cfg):
    """x [S, d] (normed) → [S, d]."""
    S = x.shape[0]
    H, Hkv, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, rot = cfg["rms_norm_eps"], int(D * cfg["partial_rotary_factor"])
    qg = (x @ w["wq"]).reshape(S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (x @ w["wk"]).reshape(S, Hkv, D)
    v = (x @ w["wv"]).reshape(S, Hkv, D)
    q = rms_norm(q, 1.0 + w["q_norm"], eps)
    k = rms_norm(k, 1.0 + w["k_norm"], eps)
    q, k = (jnp.concatenate([rope(a[..., :rot], pos[:, None],
                                  cfg["rope_theta"]), a[..., rot:]], -1)
            for a in (q, k))
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    o = attention(q, k, v, seg, float(1.0 / np.sqrt(D)))
    return (o * jax.nn.sigmoid(gate)).reshape(S, H * D) @ w["wo"]


# -- experts -------------------------------------------------------------------


def route(router, x, valid, k):
    """x [S, d] → (gate over ALL the router's experts [S, E], zero
    where not selected or a padding row; the router's load [E])."""
    p = jax.nn.softmax(x @ router, axis=-1)
    picked, ids = jax.lax.top_k(p, k)
    chosen = jax.nn.one_hot(ids, router.shape[1], dtype=p.dtype)
    gate = (chosen * (picked / picked.sum(-1, keepdims=True))[..., None]
            ).sum(1)
    load = (chosen.sum(1) * valid[:, None]).sum(0)
    return gate * valid[:, None], load.astype(jnp.float32)


def swiglu(w, x):
    return (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]


def experts(ex, m, gate, held):
    """Every held expert's SwiGLU on every token, weighted by its gate:
    m [S, d] (normed), gate [S, E] → this share's part [S, d]."""
    held = jnp.asarray(list(range(gate.shape[1])) if held is None
                       else list(held))
    S = m.shape[0]
    nb = _blocks(S, ROW_BLOCK)

    @jax.checkpoint
    def rows(args):
        m, gate = args
        out = jnp.einsum(
            "esf,efd->esd",
            jax.nn.silu(jnp.einsum("sd,edf->esf", m, ex["wg"]))
            * jnp.einsum("sd,edf->esf", m, ex["wu"]), ex["wd"])
        return jnp.einsum("esd,se->sd", out, gate)

    return jax.lax.map(rows, (m.reshape(nb, S // nb, -1),
                              gate[:, held].reshape(nb, S // nb, -1))
                       ).reshape(m.shape)


def moe(w, m, valid, held, cfg):
    """m [S, d] (normed) → (this share's part of the expert layer's
    result, the router's load)."""
    gate, load = route(w["router"], m, valid, cfg["num_experts_per_tok"])
    shared = jax.nn.sigmoid(m @ w["shared_gate"])[:, None] * swiglu(
        w["shared"], m)
    return experts(w["experts"], m, gate, held) + shared, load


def layer(w, x, seg, pos, held, cfg):
    """One layer on x [S, d] → (x'', the router's load); its kind is
    read off its weights."""
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, 1.0 + w["op_norm"], eps)
    if "gdn" in w:
        x = x + gated_delta_net(w["gdn"], a, seg, cfg)
    else:
        x = x + gated_attention(w["attn"], a, seg, pos, cfg)
    y, load = moe(w, rms_norm(x, 1.0 + w["ffn_norm"], eps),
                  (seg > 0).astype(x.dtype), held, cfg)
    return x + y, load


def forward(weights, bias, seq, cfg, held=None, wrap=lambda f: f,
            dtype=jnp.float32):
    """ONE packed sequence (``seq``: tokens, seg, pos [S] int32) →
    (logits [S, V] float32, loads [layers, E]). ``bias`` is ignored."""
    del bias
    w = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), weights)
    seg, pos = seq["seg"], seq["pos"]
    x = w["embed"][seq["tokens"]]
    loads = []
    for run in w["runs"]:
        x, load = jax.lax.scan(
            lambda x, wl: wrap(lambda wl, x: layer(
                wl, x, seg, pos, held, cfg))(wl, x), x, run)
        loads.append(load)
    logits = rms_norm(x, 1.0 + w["final_norm"],
                      cfg["rms_norm_eps"]) @ w["head"]
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
