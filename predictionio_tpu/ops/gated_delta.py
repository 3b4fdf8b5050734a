"""The gated delta rule along the segments of a packed sequence, chunk
by chunk: the chunks' triangular systems as batched products, the walk
over them as a pair of Pallas kernels that keep the state in VMEM.

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
[β ⊙ V | β ⊙ e^γ ⊙ K] — made for every chunk of a block at once, off
the sequential path (:func:`solve_unit_lower`: the inverse (I + A)⁻¹
from its 16-row blocks, then ONE product with the wide right-hand
side) — and the walk over the chunks is three small products a chunk
(the two that read S₀ are one) a head (:func:`_walk`).

- **The walk** takes one of two forms that the operands' SHAPES choose
  (:func:`walk_form`; no flag). Where a chunk is whole 8-row tiles and
  the state whole 128-lane tiles (the benchmark cell: 64 rows, 128 ×
  128) it is ONE function with its own ``jax.custom_vjp`` whose forward
  and whose reverse are Pallas kernels, the grid of each the walk —
  (blocks of :data:`WALK_HEADS` heads, the chunks in order): the heads'
  state (in reverse: its cotangent) stays in VMEM scratch from chunk to
  chunk and a chunk's solved operands stream in, where an XLA ``while``
  paid the state's round trip through HBM and a handful of small
  fusions a trip (25 µs a chunk step forward, 49 µs in reverse, against
  11 and 21 in the kernels: PERF.md §6, PR 53). The reverse kernel
  recomputes δ from the state that entered the chunk, which the
  differentiated forward keeps ([M, H, d_k, d_v] a block) and the plain
  forward does not write. Compiled for a TPU and interpreted anywhere
  else, decided when the program is LOWERED. Any other shape walks the
  same lines under ``lax.scan``, differentiated by JAX.

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
  largest single error of the model's logits) — in the kernels too:
  float32 operands, ``HIGHEST`` products, a float32 state in VMEM. Keys
  and queries come normalised (the layer's affair).

Keys and queries may have FEWER heads than the values: with ``Hk`` key
heads, ``r = H ÷ Hk`` adjacent value heads read key head ``h ÷ r``.
A block's preparation is plain ``jax.numpy``; the backward pass is
``jax.vjp`` of a block — which meets the walk's own rule —, walked over
the blocks in reverse by the rule's own backward (:func:`_backward`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a checkpointed block of the walk (a multiple of the chunk)
BLOCK_ROWS = 2048
#: rows of a diagonal block of a chunk's triangular system
SOLVE_BLOCK = 16
#: the names the rule's forward gives its output and the states that
#: enter its blocks: what a caller's checkpoint policy may keep
KEPT = ("gdn_out", "gdn_states")
#: value heads a grid step of the walk's kernels holds: at 128 × 128 the
#: state 0.5 MB and a chunk's operands 1.4 MB (forward; the reverse
#: kernel's 4.4 MB), twice for the pipeline, and the products' results —
#: 7.1 and 14.3 MB as the chip's compiler counts them, inside its DEFAULT
#: scoped fast memory (16 MiB), which a kernel that asks for more takes
#: from the rest of the program's tiling
WALK_HEADS = 8
_LANES = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_product = functools.partial(jnp.einsum, precision=_HIGHEST)
_kernel_product = functools.partial(_product,
                                    preferred_element_type=jnp.float32)


def runs_of(seg, xp):
    """Per row of ``seg`` [S], the number of its run of equal ids,
    counted from 1: rows i and t are of one segment ⇔ their runs are
    equal. ``xp``: numpy on the host, jax.numpy in a program."""
    start = xp.concatenate([xp.ones(1, bool), seg[1:] != seg[:-1]])
    return xp.cumsum(start.astype(xp.int32))


def unit_lower_inverse(lower):
    """(I + L)⁻¹ [..., C, C] of L = ``lower`` STRICTLY lower
    triangular, float32, as batched products on blocks of
    :data:`SOLVE_BLOCK` rows. The diagonal blocks are inverted exactly
    by doubling — a strictly lower b × b matrix is nilpotent, so
    (I + D)⁻¹ = (I − D)(I + D²)(I + D⁴)… ends after log₂ b factors; at
    b = 16 the powers stay small where those of a whole chunk would
    not — and block row i of the inverse follows from the rows above
    it: T[i, :i] = −T[i, i] · L[i, :i] · T[:i, :i]."""
    C = lower.shape[-1]
    b = math.gcd(SOLVE_BLOCK, C)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    eye = jnp.eye(b, dtype=lower.dtype)
    diagonal = jnp.stack([lower[..., i:i + b, i:i + b]
                          for i in range(0, C, b)], axis=-3)
    inverse, power, reach = eye - diagonal, diagonal, 1
    while 2 * reach < b:
        power, reach = mm(power, power), 2 * reach
        inverse = mm(inverse, eye + power)
    rows = []
    for n, i in enumerate(range(0, C, b)):
        own = [inverse[..., n, :, :],
               jnp.zeros(lower.shape[:-2] + (b, C - i - b), lower.dtype)]
        if rows:
            above = jnp.concatenate(rows, -2)[..., :i]
            own.insert(0, -mm(own[0], mm(lower[..., i:i + b, :i], above)))
        rows.append(jnp.concatenate(own, -1))
    return jnp.concatenate(rows, axis=-2)


def solve_unit_lower(lower, rhs):
    """x of (I + L) x = ``rhs``, L = ``lower`` [..., C, C] STRICTLY
    lower triangular, ``rhs`` [..., C, n], float32: the inverse
    (:func:`unit_lower_inverse`: small products on 16-row blocks, a
    C × C result a system) times ``rhs`` — ONE product over the wide
    right-hand side, where substitution block row by block row read
    and wrote it in 16-row strips (the chip's own triangular solve
    walks a matrix row by row: 2.7 µs a 64 × 64 system).
    Differentiated by JAX, product by product."""
    return jnp.matmul(unit_lower_inverse(lower), rhs, precision=_HIGHEST)


# -- the walk over a block's chunks ---------------------------------------------
#
# ``state`` [H, dk, dv] enters; per chunk m the solved operands ``u``
# [M, H, C, dv], ``wq`` = [W ; e^γ ⊙ Q] [M, H, 2C, dk], ``p`` [M, H, C, C],
# ``k_out`` [M, H, C, dk] and ``keep`` [M, H]:
#
#     read = wq·S;  δ = u − read[:C];  out = read[C:] + p·δ
#     S ← keep·S + k_outᵀ·δ
#
# and in reverse, with the state S that ENTERED the chunk, ``d_out`` and
# the cotangent dS′ of the state that left it:
#
#     dδ = pᵀ·d_out + k_out·dS′;  d_read = [−dδ ; d_out]
#     d_u = dδ;  d_wq = d_read·Sᵀ;  d_p = d_out·δᵀ;  d_k_out = δ·dS′ᵀ
#     d_keep = ⟨dS′, S⟩;  dS = keep·dS′ + wqᵀ·d_read
#
# Two forms, chosen by the operands' shapes (:func:`walk_form`): Pallas
# kernels whose grid IS the walk — the heads' state (its cotangent) stays
# in VMEM from chunk to chunk, a chunk's operands stream in —, forward
# and reverse behind one ``custom_vjp``; and the forward's lines under
# ``lax.scan``, JAX's to differentiate, for shapes the kernels do not take.

def walk_form(C: int, dk: int, dv: int) -> str:
    """``"kernel"`` where the walk's kernels take a chunk of ``C`` rows
    and a ``dk`` × ``dv`` state — whole 128-lane tiles of state, whole
    8-row tiles of a chunk — else ``"scan"``."""
    whole = C % 8 == 0 and dk % _LANES == 0 and dv % _LANES == 0
    return "kernel" if whole else "scan"


def _scan_walk(state, u, wq, p, k_out, keep):
    """The plain form: a ``lax.scan`` over the chunks, differentiated
    by JAX."""
    C = u.shape[2]

    def step(state, xs):
        u, wq, p, k_out, keep = xs
        read = _product("hcd,hdv->hcv", wq, state)  # [W S₀ ; (e^γ ⊙ Q) S₀]
        delta = u - read[:, :C]
        out = read[:, C:] + _product("hct,htv->hcv", p, delta)
        return keep[:, None, None] * state + _product(
            "hcd,hcv->hdv", k_out, delta), out

    return jax.lax.scan(step, state, (u, wq, p, k_out, keep))


def _forward_kernel(state_ref, u_ref, wq_ref, p_ref, k_out_ref, keep_ref,
                    left_ref, out_ref, *rest):
    """One chunk of a block of heads, the heads one batched product;
    ``rest``: the state in VMEM, and before it (when kept) the block
    that takes the entering state."""
    *entering_ref, at = rest
    m, C = pl.program_id(1), u_ref.shape[1]

    @pl.when(m == 0)
    def _():
        at[...] = state_ref[...]

    state = at[...]
    for ref in entering_ref:
        ref[...] = state
    read = _kernel_product("hcd,hdv->hcv", wq_ref[...], state)
    delta = u_ref[...] - read[:, :C]
    out_ref[...] = read[:, C:] + _kernel_product(
        "hct,htv->hcv", p_ref[...], delta)
    at[...] = keep_ref[...] * state + _kernel_product(
        "hcd,hcv->hdv", k_out_ref[...], delta)

    @pl.when(m == pl.num_programs(1) - 1)
    def _():
        left_ref[...] = at[...]


def _reverse_kernel(d_left_ref, entering_ref, u_ref, wq_ref, p_ref, k_out_ref,
                    keep_ref, d_out_ref, d_state_ref, d_u_ref, d_wq_ref,
                    d_p_ref, d_k_out_ref, d_keep_ref, at):
    """One chunk of a block of heads, the chunks in reverse; ``at``:
    the state's cotangent in VMEM. ``d_keep`` leaves as its sums over
    d_k, a row of lanes a head."""
    m, C = pl.program_id(1), u_ref.shape[1]

    @pl.when(m == 0)
    def _():
        at[...] = d_left_ref[...]

    state, d_state = entering_ref[...], at[...]
    wq, d_out = wq_ref[...], d_out_ref[...]
    delta = u_ref[...] - _kernel_product("hcd,hdv->hcv", wq[:, :C], state)
    d_delta = (_kernel_product("hct,hcv->htv", p_ref[...], d_out)
               + _kernel_product("hcd,hdv->hcv", k_out_ref[...], d_state))
    d_read = jnp.concatenate([-d_delta, d_out], axis=1)
    d_u_ref[...] = d_delta
    d_wq_ref[...] = _kernel_product("hcv,hdv->hcd", d_read, state)
    d_p_ref[...] = _kernel_product("hcv,htv->hct", d_out, delta)
    d_k_out_ref[...] = _kernel_product("hcv,hdv->hcd", delta, d_state)
    d_keep_ref[...] = (d_state * state).sum(1, keepdims=True)
    at[...] = keep_ref[...] * d_state + _kernel_product(
        "hcd,hcv->hdv", wq, d_read)

    @pl.when(m == pl.num_programs(1) - 1)
    def _():
        d_state_ref[...] = at[...]


def _walk_call(kernel, name, reverse, state_like, chunked, outs, interpret):
    """One kernel over the grid (blocks of heads, the chunks in order —
    ``reverse``: from the last): ``state_like`` [H, dk, dv] operands
    are fetched once a block of heads, ``chunked`` [M, H, rows, width]
    a chunk a grid step; ``outs``: shapes of either kind."""
    M, H = chunked[0].shape[:2]
    hb = math.gcd(H, WALK_HEADS)

    def spec(x):
        if len(x.shape) == 3:
            return pl.BlockSpec((hb,) + x.shape[1:], lambda h, m: (h, 0, 0))
        return pl.BlockSpec(
            (None, hb) + x.shape[2:],
            lambda h, m: (M - 1 - m if reverse else m, h, 0, 0))

    return pl.pallas_call(
        kernel, name=name, interpret=interpret, grid=(H // hb, M),
        in_specs=[spec(x) for x in (*state_like, *chunked)],
        out_shape=outs, out_specs=[spec(x) for x in outs],
        scratch_shapes=[pltpu.VMEM((hb,) + state_like[0].shape[1:],
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*state_like, *chunked)


def _lanes(keep, dv: int):
    """``keep`` [M, H] → [M, H, 1, dv]: a head's number as a row of
    lanes, which multiplies its state's rows as it is."""
    return jnp.broadcast_to(keep[..., None, None], keep.shape + (1, dv))


def _kernel_forward(state, u, wq, p, k_out, keep, keeps: bool, interpret):
    f32 = jnp.float32
    outs = [jax.ShapeDtypeStruct(state.shape, f32),
            jax.ShapeDtypeStruct(u.shape, f32)]
    if keeps:
        outs.append(jax.ShapeDtypeStruct(u.shape[:2] + state.shape[1:], f32))
    return tuple(_walk_call(
        _forward_kernel, "gdn_walk_fwd", False, (state,),
        (u, wq, p, k_out, _lanes(keep, u.shape[-1])), outs, interpret))


def _kernel_reverse(entering, u, wq, p, k_out, keep, d_left, d_out,
                    interpret):
    dv = u.shape[-1]
    lanes = _lanes(keep, dv)
    *d, d_keep = _walk_call(
        _reverse_kernel, "gdn_walk_bwd", True, (d_left,),
        (entering, u, wq, p, k_out, lanes, d_out),
        [jax.ShapeDtypeStruct(x.shape, jnp.float32)
         for x in (d_left, u, wq, p, k_out, lanes)], interpret)
    return (*d, d_keep.sum((-2, -1)))


def _by_platform(kernel, *args):
    """``kernel`` compiled for a TPU and interpreted anywhere else,
    decided when the program is LOWERED (as ``ops/seq_attention.py``
    does)."""
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))


@jax.custom_vjp
def _kernel_walk(state, u, wq, p, k_out, keep):
    return _by_platform(functools.partial(_kernel_forward, keeps=False),
                        state, u, wq, p, k_out, keep)


def _kernel_walk_fwd(state, u, wq, p, k_out, keep):
    left, out, entering = _by_platform(
        functools.partial(_kernel_forward, keeps=True),
        state, u, wq, p, k_out, keep)
    return (left, out), (entering, u, wq, p, k_out, keep)


def _kernel_walk_bwd(kept, cotangents):
    return _by_platform(_kernel_reverse, *kept, *cotangents)


_kernel_walk.defvjp(_kernel_walk_fwd, _kernel_walk_bwd)


def _walk(state, u, wq, p, k_out, keep):
    """(the state that leaves the block, the chunks' output [M, H, C,
    dv]), in the form the shapes choose (:func:`walk_form`). The
    kernels are a ``jax.custom_vjp``: differentiated, the forward also
    keeps the state that ENTERS each chunk ([M, H, dk, dv]) for the
    reverse walk; plain, it writes none."""
    if walk_form(u.shape[2], wq.shape[-1], u.shape[-1]) == "scan":
        return _scan_walk(state, u, wq, p, k_out, keep)
    return _kernel_walk(state, u, wq, p, k_out, keep)


def _block(carry, xs, chunk: int):
    """One block of rows: (the state [H, dk, dv] and the run that
    enter it) → (those that leave it), and its rows' output."""
    state, run_before = carry
    q, k, v, g, beta, run = xs
    R, H, dv = v.shape
    Hk = k.shape[1]
    r, C, M = H // Hk, chunk, R // chunk
    f32 = jnp.float32

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
    kk, qk = (jnp.repeat(_product("mhtd,mhid->mhti", a, kc), r, axis=1)
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

    state, out = _walk(state, u, jnp.concatenate([w, q_in], axis=2), p,
                       k_out, keep)
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
    rule = jax.custom_vjp(_rule, nondiff_argnums=(6,))
    rule.defvjp(_rule_fwd, _backward)
    return rule(q, k, v, g, beta, seg, chunk)
