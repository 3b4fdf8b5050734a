"""The gated delta rule along the segments of a packed sequence, as a
chunked scan.

A linear-attention layer of the Gated-DeltaNet kind carries, per value
head, a state ``S`` [d_k, d_v] along the sequence. Row t (key ``k_t``
and query ``q_t`` [d_k], value ``v_t`` [d_v], log-decay ``g_t`` ≤ 0,
write strength ``β_t``) does

    S ← e^{g_t} S;   δ_t = β_t (v_t − Sᵀ k_t);   S ← S + k_t δ_tᵀ;
    o_t = Sᵀ q_t

and ``S = 0`` at the first row of every SEGMENT (a maximal run of one
id in ``seg``: a packed sequence holds many histories back to back,
``models/seq_backbone.pack_histories``; the padding behind them is a
run like any other, which no real row reads).

Row by row that is ``S`` sequential steps of a [d_k, d_v] update. Here
it goes ``chunk`` rows at a time, exactly (the WY form of the delta
rule): with γ_t = Σ_{i ≤ t} g_i inside a chunk and ``S₀`` the state
that enters it,

    (I + A) Δ = β ⊙ V − (β ⊙ e^γ ⊙ K) S₀,
                      A[t, i] = β_t e^{γ_t − γ_i} (k_t · k_i), i < t
    O = (e^γ ⊙ Q) S₀ + P Δ,     P[t, i] = e^{γ_t − γ_i} (q_t · k_i), i ≤ t
    S_C = e^{γ_C} S₀ + (e^{γ_C − γ} ⊙ K)ᵀ Δ

so Δ = U − W S₀ with U, W one unit-lower-triangular solve against
[β ⊙ V | β ⊙ e^γ ⊙ K] — made for every chunk at once, off the
sequential path, as batched products (:func:`solve_unit_lower`) — and the walk over the chunks is three small products
a chunk (the two that read S₀ are one), batched over the heads.

- **Segments.** A boundary inside a chunk zeroes, by MASKS, every
  in-chunk pair (t, i) that straddles it (in A and P), the entering
  state for the rows behind it (in W and e^γ ⊙ Q) and, for the state
  that leaves, the entering state and the rows before the chunk's last
  boundary. A segment so gets exactly what it gets alone, and a chunk
  may hold any number of starts.
- **Decays** enter as ``exp`` of DIFFERENCES of γ inside a chunk, each
  ≤ 0, in float32 — never as a quotient of exponentials: γ reaches
  −1,300 in a chunk of 64 and e^{−γ} is not a float32.
- **Memory.** The walk is cut into blocks of :data:`BLOCK_ROWS` rows.
  The rule is a ``jax.custom_vjp``: its forward hands the backward
  pass the operands and the state that ENTERS each block (64 KB a head
  at 128 × 128), and names the output ``"gdn_out"`` and those states
  ``"gdn_states"`` (``jax.ad_checkpoint.checkpoint_name``). A CALLER
  that runs the rule under ``jax.checkpoint`` with the policy
  ``save_only_these_names("gdn_out", "gdn_states")`` keeps the two and
  its backward pass does not walk forward again; without a policy the
  checkpoint recomputes the forward, as it does everything else. Inside
  the block being differentiated what is kept is the state that enters
  each chunk — never a state a row, and nothing of a chunk's triangular
  matrices outside the block at work.
- **Precision.** float32 throughout, the products at
  ``Precision.HIGHEST`` (six bfloat16 passes of the chip's multiplier):
  inside a chunk the state is never written down — P Δ and (e^{γ_C − γ}
  ⊙ K)ᵀ Δ ARE it — so operands rounded to bfloat16 there would be a
  state rounded at every row (measured: 2.6e-3 of a layer's output, the
  largest single error of the model's logits), and the products are
  small: the walk is bound by latency, not by the multiplier. Keys and
  queries come normalised (the layer's affair).

Keys and queries may have FEWER heads than the values: with ``Hk`` key
heads, ``r = H ÷ Hk`` adjacent value heads read key head ``h ÷ r``.
Plain ``jax.numpy`` under ``lax.scan``; the backward pass is ``jax.vjp``
of a block, walked over the blocks in reverse by the rule's own
backward (:func:`_backward`).
"""

from __future__ import annotations

import math

#: rows of a checkpointed block of the walk (a multiple of the chunk)
BLOCK_ROWS = 2048
#: rows of a diagonal block of a chunk's triangular system
SOLVE_BLOCK = 16
#: the names the rule's forward gives its output and the states that
#: enter its blocks: what a caller's checkpoint policy may keep
KEPT = ("gdn_out", "gdn_states")


def runs_of(seg, xp):
    """Per row of ``seg`` [S], the number of its run of equal ids,
    counted from 1: rows i and t are of one segment ⇔ their runs are
    equal. ``xp``: numpy on the host, jax.numpy in a program."""
    start = xp.concatenate([xp.ones(1, bool), seg[1:] != seg[:-1]])
    return xp.cumsum(start.astype(xp.int32))


def solve_unit_lower(lower, rhs):
    """x of (I + L) x = ``rhs``, L = ``lower`` [..., C, C] STRICTLY
    lower triangular, ``rhs`` [..., C, n], float32 — as batched
    products (the chip's own triangular solve walks a matrix row by
    row: 2.7 µs a 64 × 64 system, half the scan's time). The diagonal
    blocks of :data:`SOLVE_BLOCK` rows are inverted exactly by
    doubling — a strictly lower b × b matrix is nilpotent, so
    (I + D)⁻¹ = (I − D)(I + D²)(I + D⁴)… ends after log₂ b factors; at
    b = 16 the powers stay small where those of a whole chunk would
    not — and the block rows follow by forward substitution."""
    import functools

    import jax
    import jax.numpy as jnp

    C = lower.shape[-1]
    b = math.gcd(SOLVE_BLOCK, C)
    mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    eye = jnp.eye(b, dtype=lower.dtype)
    diagonal = jnp.stack([lower[..., i:i + b, i:i + b]
                          for i in range(0, C, b)], axis=-3)
    inverse, power, reach = eye - diagonal, diagonal, 1
    while 2 * reach < b:
        power, reach = mm(power, power), 2 * reach
        inverse = mm(inverse, eye + power)
    rows = []
    for n, i in enumerate(range(0, C, b)):
        r = rhs[..., i:i + b, :]
        if rows:
            r = r - mm(lower[..., i:i + b, :i], jnp.concatenate(rows, -2))
        rows.append(mm(inverse[..., n, :, :], r))
    return jnp.concatenate(rows, axis=-2)


def _block(carry, xs, chunk: int):
    """One block of rows: (the state [H, dk, dv] and the run that
    enter it) → (those that leave it), and its rows' output."""
    import functools

    import jax
    import jax.numpy as jnp

    state, run_before = carry
    q, k, v, g, beta, run = xs
    R, H, dv = v.shape
    Hk = k.shape[1]
    r, C, M = H // Hk, chunk, R // chunk
    f32 = jnp.float32
    product = functools.partial(jnp.einsum,
                                precision=jax.lax.Precision.HIGHEST)

    run = run.reshape(M, C)
    prev = jnp.concatenate([run_before[None], run[:-1, -1]])
    enters = run == prev[:, None]               # the row reads S₀
    leaves = run == run[:, -1:]                 # the row writes S_C
    at = jnp.arange(C)
    pair = ((run[:, :, None] == run[:, None, :])
            & (at[:, None] >= at[None, :]))[:, None]        # [M, 1, C, C]

    def heads(x):                               # [R, H, …] → [M, H, C, …]
        return jnp.moveaxis(x.reshape((M, C) + x.shape[1:]), 2, 1)

    gamma = jnp.cumsum(heads(g.astype(f32)), axis=-1)       # [M, H, C]
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(pair, jnp.exp(jnp.where(pair, diff, 0.0)), 0.0)
    e_in = jnp.exp(gamma) * enters[:, None]
    e_out = jnp.exp(gamma[..., -1:] - gamma) * leaves[:, None]
    keep = jnp.exp(gamma[..., -1]) * enters[:, -1:]         # [M, H]

    kc, qc = (heads(x.astype(f32)) for x in (k, q))         # [M, Hk, C, dk]
    kk, qk = (jnp.repeat(product("mhtd,mhid->mhti", a, kc), r, axis=1)
              for a in (kc, qc))
    b = heads(beta.astype(f32))                             # [M, H, C]
    kh, qh = (jnp.repeat(a, r, axis=1) for a in (kc, qc))
    # (I + A) [U | W] = [β ⊙ V | β ⊙ e^γ ⊙ K], A strictly lower
    solved = solve_unit_lower(
        b[..., None] * kk * decay * (at[:, None] > at[None, :]),
        jnp.concatenate([b[..., None] * heads(v.astype(f32)),
                         (b * e_in)[..., None] * kh], axis=-1))
    u, w = solved[..., :dv], solved[..., dv:]
    p = qk * decay
    q_in = qh * e_in[..., None]
    k_out = kh * e_out[..., None]

    def step(state, xs):
        u, wq, p, k_out, keep = xs
        read = product("hcd,hdv->hcv", wq, state)   # [W S₀ ; (e^γ ⊙ Q) S₀]
        delta = u - read[:, :C]
        out = read[:, C:] + product("hct,htv->hcv", p, delta)
        state = keep[:, None, None] * state + product(
            "hcd,hcv->hdv", k_out, delta)
        return state, out

    state, out = jax.lax.scan(
        step, state, (u, jnp.concatenate([w, q_in], axis=2), p, k_out, keep))
    return (state, run[-1, -1]), jnp.moveaxis(out, 1, 2).reshape(R, H, dv)


def block_rows(chunk: int, S: int):
    """(rows of a chunk, rows of a block) of a sequence of ``S`` rows:
    the most of ``chunk`` that divides S, and the most chunks of
    :data:`BLOCK_ROWS` rows that divide its chunks."""
    C = math.gcd(chunk, S)
    return C, C * math.gcd(max(BLOCK_ROWS // C, 1), S // C)


def _blocks(x, R: int):
    """[S, …] → [S ÷ R, R, …]: the rows of each block."""
    return x.reshape((x.shape[0] // R, R) + x.shape[1:])


def _forward(q, k, v, g, beta, seg, chunk: int):
    """The walk over the blocks, one sequence at a time: the output
    [B, S, H, dv] and the state [B, blocks, H, dk, dv] that ENTERS each
    block."""
    import jax
    import jax.numpy as jnp

    S, H, dv = v.shape[1:]
    dk = k.shape[-1]
    C, R = block_rows(chunk, S)

    def block(carry, xs):
        # the scan hands out the state that LEAVES a block (= enters
        # the next): handing out the carry that came in keeps the
        # chip's compiler from holding the walk's state in fast memory
        # (measured: the forward walk 1.30 s a train of the cell
        # against 1.20 s so)
        left, out = _block(carry, xs, C)
        return left, (out, left[0])

    def one(args):
        *rows, seg = args
        xs = tuple(_blocks(x, R) for x in (*rows, runs_of(seg, jnp)))
        zero = jnp.zeros((H, dk, dv), jnp.float32)
        out, left = jax.lax.scan(block, (zero, jnp.int32(0)), xs)[1]
        return (out.reshape(S, H, dv),
                jnp.concatenate([zero[None], left[:-1]]))

    return jax.lax.map(one, (q, k, v, g, beta, seg))


def _rule(q, k, v, g, beta, seg, chunk: int):
    return _forward(q, k, v, g, beta, seg, chunk)[0]


def _rule_fwd(q, k, v, g, beta, seg, chunk: int):
    from jax.ad_checkpoint import checkpoint_name

    out, states = (checkpoint_name(x, name)
                   for x, name in zip(_forward(q, k, v, g, beta, seg, chunk),
                                      KEPT))
    return out, (q, k, v, g, beta, seg, states)


def _backward(chunk: int, kept, d_out):
    """The blocks in REVERSE: a block's forward again from the state
    that entered it, kept chunk by chunk, then its backward walk
    (``jax.vjp`` of :func:`_block`), which hands the state's cotangent
    to the block before. ``seg`` gets none, nor does an operand of
    integers."""
    import jax
    import jax.numpy as jnp

    *rows, seg, states = kept
    S = seg.shape[1]
    C, R = block_rows(chunk, S)
    floats = [i for i, x in enumerate(rows)
              if jnp.issubdtype(x.dtype, jnp.floating)]

    def one(args):
        rows, seg, states, d_out = args
        run = _blocks(runs_of(seg, jnp), R)
        before = jnp.concatenate([jnp.zeros(1, jnp.int32), run[:-1, -1]])

        def back(d_state, xs):
            state, before, rows, run, d_out = xs

            def leaves(state, *some):
                at = dict(zip(floats, some))
                (state, _), out = _block(
                    (state, before),
                    (*(at.get(i, x) for i, x in enumerate(rows)), run), C)
                return state, out

            pull = jax.vjp(leaves, state, *(rows[i] for i in floats))[1]
            d_state, *d_rows = pull((d_state, d_out))
            return d_state, tuple(d_rows)

        d_rows = jax.lax.scan(
            back, jnp.zeros(states.shape[1:], jnp.float32),
            (states, before, tuple(_blocks(x, R) for x in rows), run,
             _blocks(d_out, R)), reverse=True)[1]
        return tuple(d.reshape((S,) + d.shape[2:]) for d in d_rows)

    d_rows = dict(zip(floats, jax.lax.map(
        one, (tuple(rows), seg, states, d_out))))
    return tuple(d_rows.get(i) for i in range(len(rows))) + (None,)


def gated_delta_rule(q, k, v, g, beta, seg, chunk: int):
    """``q``, ``k`` [B, S, Hk, dk] (normalised), ``v`` [B, S, H, dv],
    ``g``, ``beta`` [B, S, H], ``seg`` [B, S] int32 → the rule's output
    [B, S, H, dv] float32, one sequence at a time. ``chunk``: rows of a
    chunk (the most that divides S is taken)."""
    import jax

    rule = jax.custom_vjp(_rule, nondiff_argnums=(6,))
    rule.defvjp(_rule_fwd, _backward)
    return rule(q, k, v, g, beta, seg, chunk)
