"""Batched weighted Gram accumulation — the ALS inner op, as a Pallas kernel.

:func:`gather_gram` is the fused **gather→Gram** kernel
(:func:`gather_gram_xla` its XLA twin): the gather itself runs inside
the kernel. Per grid program, ``F_other`` rows are fetched tile-by-tile
into a VMEM tile using the ``other_idx`` row block (prefetched into
SMEM), the weighted normal equations accumulate in a VMEM register
block, and only the ``(R, k, k)`` / ``(R, k)`` results are written
back. The gathered ``(R, C, k)`` block never materializes in HBM and
the weighting never round-trips. What bounds it (PERF_LEDGER.jsonl,
PR 24: nine bucket shapes, widths 128 to 8192, two configurations) is
neither the MXU nor HBM but the scalar core, which fetches one 512-byte
line at a time — so the kernel is given every row's REAL length and
fetches nothing for a padded slot (PR 25), fetches sixteen lines to a
loop trip and has the next program's index block fetched while this
program runs (PR 37). HOW a line is fetched follows the table's size
(:func:`table_is_resident`, PR 41): a table that fits in VMEM is
copied there once a dispatch and a line is a vector load from it and a
store into the tile (~3–4 ns a line on the chip); a larger one stays in
HBM and a line is a DMA — a descriptor, a start, and ONE wait per set
bit of a tile's copy count (~11–12 ns a line). Same tiles, mask,
product and order of sums on both: ``A`` and ``b`` bit for bit. The
~0.85 µs of vector work a 128-slot tile costs (mask, transpose,
six-pass product) is what is left, and now the larger part.
``models/als.py _make_half`` selects the kernel via ``PIO_PALLAS_GRAM``
(see :func:`resolve_gram_mode`).

Per padded rating row r:

    A_r = Fᵣᵀ · diag(w_outer[r]) · Fᵣ     (k×k)
    b_r = Fᵣᵀ · w_b[r]                    (k)

where ``F_g[r] = F_other[other_idx[r]]`` is the (W, k) gathered factor
block. This replaces MLlib ALS's per-row BLAS ``dspr``/LAPACK ``dppsv``
normal-equation builds (reference: [U] mllib ALS NormalEquation — see
SURVEY.md §2d P2) with MXU work: two dot_generals per row block, the
weighting fused into the same kernel so the weighted copy of F never
round-trips through HBM.

Grid: one program per block of RB rows. All operands stream through
VMEM via BlockSpec pipelining (double-buffered by the Pallas runtime).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# -- fused gather→Gram ---------------------------------------------------------
#
# The XLA path (``gather_gram_xla``; ``row_grams`` in models/als.py)
# pays for the gather as a SEPARATE HLO: the (R, C, k) gathered block
# round-trips through HBM between the gather and the Gram einsum. This
# kernel moves the gather inside: the index block is DMA'd into SMEM up
# front (the scalar core needs the row ids to program the data DMAs),
# factor rows stream HBM→VMEM in T-row tiles with per-row async copies,
# and the weighted normal equations accumulate in VMEM. Where the whole
# table of lines fits in VMEM (``table_is_resident``: ML-20M's 6.8 and
# 35.5 MB, Last.fm's 69.1 and 75.3 MB at rank 64) the first program of a
# dispatch copies it there in ONE DMA and every line after that is a
# single-sublane vector load at a dynamic row and a store into the tile:
# no descriptor, no semaphore, no wait — the scalar core's part of a
# fetch is the id's SMEM load and a shift.
#
# What the chip's compiler accepts (Mosaic, v5e): a DMA slice must be a
# whole number of (1, 128) f32 lane tiles, so a ``1 × k`` copy with
# k < 128 is refused ("Slice shape along dimension 1 must be aligned to
# tiling (128)"), and bf16 rows are packed two to a sublane, so a
# single bf16 row cannot be sliced at all. The kernel therefore gathers
# LINES: F_other is viewed as (N/G, 128) f32 with G = 128 // k rows to
# a line (a free reshape of the row-major array), row ``i`` is fetched
# as line ``i // G``, and the lanes of the other G-1 rows are masked to
# zero. The Gram of the masked tile is block-diagonal — each row lands
# in the diagonal block of its slot ``i % G`` — and the G diagonal
# blocks are summed once per output row. bf16 factors are upcast
# before the call: a line is 512 B whatever the dtype, so bf16 saves
# no gather traffic here.
#
# The copy pipeline of a table that stays in HBM (PR 37; PERF.md §6 has
# the chip's numbers; a resident table's dispatch builds none of it: no
# line copies, no semaphore, no waits — steps (1) and (3) only, (1) a
# load and a store a line). Per tile of T slots the scalar core
# (1) starts the row's `live` real copies,
# _ISSUE_UNROLL to a loop trip so that the ids' SMEM loads and the
# address arithmetic of neighbouring copies overlap, (2) waits for them
# — all copies signal one semaphore and a wait takes the semaphore and
# an AMOUNT, so a stand-in descriptor of g lines retires g copies: one
# wait per set bit of `live`, ONE for a full tile (`_wait_sizes`) —
# and (3) masks and multiplies the tile. The (RB, C) index block of
# program i + 1 is fetched into a second SMEM buffer while program i
# runs. A row's tiles land in TWO tile buffers in turn. Measured on the
# chip (PR 37): starting the next tile's copies AHEAD, while this tile
# is multiplied, gives nothing (+2…4 % in every bucket but one) — by
# the time a tile's last copy is started the earlier ones have landed,
# and the fixed ~0.7 µs a row is the tile's own vector work, which the
# same instruction stream issues. But where a bucket's copies do NOT
# keep up with the issue loop (seen once, kernel alone: ML-20M's
# segmented rows of the most-rated items at 21–25 ns a copy against 15;
# inside the train that bucket keeps up), it runs 15 % quicker when a
# tile does not land in the buffer the previous tile was just read
# from, and no bucket is slower for it: hence two buffers and nothing
# started ahead.
#
# VMEM sizing (per program): 3·RB·C·4 (weights + index block) +
# 2·T·L·4 (line tiles) + (L+1)·L·4 (accumulators) + RB·kp·(kp+1)·4
# (output block), with L = max(128, kp), T = min(C, 256), RB = 8 —
# worst case (C = 8192) ≈ 1 MB, ~2 MB with the runtime's double
# buffering of the blocked operands — plus, on the resident route, the
# table itself: ONE (lines, 128) f32 scratch (not a pipelined operand,
# which would be two), for which the dispatch asks the compiler for
# _VMEM_LIMIT = 96 MiB of the v5e's 128; the rule admits a table up to
# that less 4 MiB for everything above. SMEM: two (RB, C) index blocks
# (512 KB at C = 8192 — the v5e's compiler takes it, held by
# tests/test_chip_compile.py, and the chip runs it) and two (8, 128)
# blocks of row lengths (8 KB).

_GATHER_TILE = 256  # factor rows per DMA burst (T)
_LANES = 128
_ISSUE_UNROLL = 16  # line fetches started per trip of the issue loop
#: the VMEM a dispatch with a resident table asks the v5e's compiler for
#: (of 128 MiB; ``ops/seq_attention.py`` asks the same)
_VMEM_LIMIT = 96 * 1024 * 1024
#: the most bytes of factor lines a dispatch keeps in VMEM: what it asks
#: for less the kernel's own working set ("VMEM sizing" above, ~2 MB at
#: C = 8192, doubled for room). ONE buffer — the table is a scratch the
#: first program fills, not a pipelined operand
_RESIDENT_TABLE_BYTES = _VMEM_LIMIT - 4 * 1024 * 1024


def _tile(C: int) -> int:
    """T: the slots of a bucket row that one burst of line copies
    fills — 256, or the widest divisor of a narrower or odd C."""
    T = min(C, _GATHER_TILE)
    while C % T:  # ladder widths always divide; guard odd test shapes
        T -= 1
    return T


def _wait_sizes(T: int):
    """The group sizes a tile's copies are retired in: the powers of
    two up to T, largest first. A tile of ``live`` copies takes one
    wait per set bit of ``live`` — a full tile ONE — which serves both
    the kernel's ``retire`` and the host's :func:`dma_waits`."""
    return tuple(1 << s for s in reversed(range(T.bit_length())))


def dma_waits(lengths, C: int) -> int:
    """The DMA waits the kernel makes for bucket rows of these real
    ``lengths`` at width C, counted on the host. An entity of a
    segmented bucket counts as ONE length: its rows are cut at
    multiples of C and T divides C, so its tiles are those of one long
    row."""
    T = _tile(C)

    def waits(live):
        return sum((live & g) != 0 for g in _wait_sizes(T))

    n = np.asarray(lengths).astype(np.int64)
    return int((n // T * waits(T) + waits(n % T)).sum())


def _gather_gram_kernel(idx_hbm, len_ref, idx_ref, wo_ref, wb_ref, F_hbm,
                        A_ref, b_ref, idx_smem, f_tile, accA, accB, sem_idx,
                        via, *, RB: int, C: int, T: int, kp: int,
                        G: int, resident: bool):
    """``via`` is what a line is fetched through: the line copies' DMA
    semaphore, or — ``resident`` — the VMEM scratch that holds the
    whole table of factor lines for the dispatch."""
    i = pl.program_id(0)
    L = f_tile.shape[2]
    ib = i & 1

    if resident:
        # the first program fills the table, ONE copy at HBM speed; the
        # grid runs in order and scratch persists, so every later
        # program reads it where it lies
        @pl.when(i == 0)
        def _():
            pltpu.sync_copy(F_hbm, via)

    # index block HBM→SMEM: row ids live on the scalar core, which
    # issues the factor-line DMAs below. Two buffers: program i waits
    # for the block program i - 1 started (the first starts its own)
    # and starts program i + 1's — the grid runs in order
    def idx_copy(step, buf):
        return pltpu.make_async_copy(
            idx_hbm.at[pl.ds(step * RB, RB), :], idx_smem.at[buf],
            sem_idx.at[buf])

    @pl.when(i == 0)
    def _():
        idx_copy(0, 0).start()

    idx_copy(0, ib).wait()

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        idx_copy(i + 1, 1 - ib).start()

    shift = G.bit_length() - 1  # row → line: G is a power of two

    def issue(r, base, live, buf):
        """Fetch the `live` lines of one tile — slots
        [base, base + live) of block row r — into tile buffer `buf`:
        from the resident table a vector load and store a line, else a
        line copy started (all signal the one semaphore and have the
        same (1, L) shape; ``retire`` waits for them). The loop is
        unrolled: a trip fetches _ISSUE_UNROLL lines, the remainder
        goes one by one."""
        def one(j):
            row = idx_smem[ib, r, base + j]
            line = pl.ds(jax.lax.shift_right_logical(row, shift), 1)
            if resident:
                f_tile[buf, pl.ds(j, 1), :] = via[line, :]
            else:
                pltpu.make_async_copy(F_hbm.at[line, :],
                                      f_tile.at[buf, pl.ds(j, 1), :],
                                      via).start()

        def burst(q, _):
            for u in range(_ISSUE_UNROLL):
                one(q * _ISSUE_UNROLL + u)
            return 0

        def single(j, _):
            one(j)
            return 0

        bursts = live // _ISSUE_UNROLL
        jax.lax.fori_loop(0, bursts, burst, 0)
        jax.lax.fori_loop(bursts * _ISSUE_UNROLL, live, single, 0)

    def retire(live, buf):
        """Wait until the tile's `live` copies have landed: a wait
        takes the semaphore and an AMOUNT, so a stand-in descriptor of
        g lines retires g copies at once — one wait per set bit of
        `live` (``_wait_sizes``)."""
        for g in _wait_sizes(T):
            @pl.when((live & g) != 0)
            def _(g=g):
                pltpu.make_async_copy(
                    F_hbm.at[pl.ds(0, g), :],
                    f_tile.at[buf, pl.ds(0, g), :],
                    via).wait()

    tile_row = jax.lax.broadcasted_iota(jnp.int32, (T, L), 0)
    lane_slot = jax.lax.broadcasted_iota(jnp.int32, (T, L), 1) // kp
    # this program's RB lengths within the (8, 128) block of lengths
    # (RB consecutive lanes of one line: RB divides 128)
    len_at = (i * RB) % (8 * _LANES)
    len_line, len_lane = len_at // _LANES, len_at % _LANES
    def row_body(r, _):
        accA[...] = jnp.zeros((L, L), jnp.float32)
        accB[...] = jnp.zeros((1, L), jnp.float32)
        n = len_ref[len_line, len_lane + r]

        def tile_body(t, _):
            # only the row's real slots are fetched
            live = jnp.minimum(n - t * T, T)
            # a row's tiles land in the two tile buffers in turn
            buf = t & 1
            issue(r, t * T, live, buf)
            if not resident:
                retire(live, buf)
            # tile rows past `live` still hold what an earlier tile or
            # row fetched into this buffer (or nothing yet): masked by
            # ROW, because a zero weight does not make a stale inf or
            # NaN a zero
            keep = tile_row < live
            if G > 1:
                slot = idx_ref[r, pl.ds(t * T, T)] % G
                keep &= lane_slot == slot[:, None]
            F = jnp.where(keep, f_tile[buf], 0.0)
            wo = wo_ref[r, pl.ds(t * T, T)]
            wb = wb_ref[r, pl.ds(t * T, T)]
            # f32 normal equations (+13% kernel time over bf16, Gram
            # error 6e-5 vs 3e-1, and the Cholesky solve amplifies it)
            accA[...] += jax.lax.dot_general(
                F * wo[:, None], F, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
            accB[...] += jnp.sum(F * wb[:, None], axis=0, keepdims=True)
            return 0

        # tiles past the row's last real slot are not visited; a row of
        # length 0 (slab padding) copies nothing and writes zeros
        jax.lax.fori_loop(0, (n + T - 1) // T, tile_body, 0)
        A = accA[0:kp, 0:kp]
        b = accB[:, 0:kp]
        for g in range(1, G):  # fold the slots' diagonal blocks
            A = A + accA[g * kp:(g + 1) * kp, g * kp:(g + 1) * kp]
            b = b + accB[:, g * kp:(g + 1) * kp]
        A_ref[r] = A
        b_ref[pl.ds(r, 1), :] = b
        return 0

    # the block's RB rows in a LOOP, not unrolled: one copy of the
    # row's code keeps the kernel small for the chip's compiler
    jax.lax.fori_loop(0, RB, row_body, 0)


def gather_gram_xla(F_other, idx, wo, wb):
    """XLA fallback with the kernel's contract: gather then weighted
    Gram. F_other (N, k), idx (R, C) int32, wo/wb (R, C) →
    A (R, k, k) f32, b (R, k) f32."""
    F = F_other[idx].astype(jnp.float32)           # (R, C, k)
    A = jnp.einsum("rc,rck,rcl->rkl", wo, F, F,
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("rc,rck->rk", wb, F,
                   preferred_element_type=jnp.float32)
    return A, b


def kernel_takes_width(C: int) -> bool:
    """The kernel takes a bucket whose width is a whole number of
    128-lane tiles. The ALS ladder's two narrow widths (8, 32) stay on
    the XLA gather + einsum: their (RB, C) index block cannot be sliced
    into SMEM ("Slice shape along dimension 1 must be aligned to tiling
    (128)", the v5e's compiler)."""
    return C % _LANES == 0


def _line_width(k: int):
    """(kp, G): the padded row width and rows per 128-lane line — kp is
    the smallest divisor of 128 that holds k (or a multiple of 128)."""
    if k >= _LANES:
        return -(-k // _LANES) * _LANES, 1
    kp = 8
    while kp < k:
        kp *= 2
    return kp, _LANES // kp


def _table_lines(n_other: int, k: int) -> int:
    """The 128-lane lines the kernel lays ``n_other`` factor rows of
    rank k out in: G rows to a line, and at least a tile's worth — the
    group waits' stand-in source is lines [0, g), g ≤ T."""
    _, G = _line_width(k)
    return max(-(-n_other // G), _GATHER_TILE)


def table_bytes(n_other: int, k: int) -> int:
    """The bytes of ``n_other`` factor rows of rank k as the kernel
    lays them out: float32 lines of G rows × kp lanes."""
    kp, G = _line_width(k)
    return _table_lines(n_other, k) * kp * G * 4


def table_is_resident(n_other: int, k: int) -> bool:
    """Whether a dispatch that gathers from ``n_other`` factor rows of
    rank k keeps the table in VMEM and fetches a line with a vector
    load, not a copy: the table's bytes as the kernel lays it out
    against ``_RESIDENT_TABLE_BYTES``. A shape the call observes — the
    kernel branches on it and ``ALSPrepared.kernel_rows`` counts by
    it; nothing else chooses the route."""
    return table_bytes(n_other, k) <= _RESIDENT_TABLE_BYTES


def gather_gram(F_other, idx, wo, wb, lengths, *,
                interpret: bool = False):
    """Fused gather→weighted-Gram: ONE Pallas kernel computing

        A[r] = Σ_c wo[r,c] · F[idx[r,c]] ⊗ F[idx[r,c]]
        b[r] = Σ_c wb[r,c] · F[idx[r,c]]

    without ever materializing the gathered (R, C, k) block in HBM.
    ``lengths`` (R,) int32 says how many LEADING slots of each row hold
    an interaction: only those are fetched and summed (the rest must
    carry zero weight — ``models/als.py _bucket_side`` pads rows at
    their end).
    ``F_other`` may be f32 or bf16 (bf16 rows are upcast before the
    call — see the block comment above). ``interpret=True`` runs the
    Mosaic interpreter (CPU tests).
    """
    R, C = idx.shape
    N, k = F_other.shape
    if R == 0:
        return (jnp.zeros((0, k, k), jnp.float32),
                jnp.zeros((0, k), jnp.float32))
    T = _tile(C)
    # lines of G rows × kp lanes (a pure reshape when k divides 128 and
    # N divides G — rank 64 on an even catalog)
    kp, G = _line_width(k)
    L = kp * G
    F = F_other.astype(jnp.float32)
    lines = _table_lines(N, k)
    if kp != k or lines * G != N:
        F = jnp.pad(F, [(0, lines * G - N), (0, kp - k)])
    F = F.reshape(lines, L)
    resident = table_is_resident(N, k)
    # Mosaic block mappings need the row-block dim divisible by 8 (or
    # equal to R): pad the row count up and slice the results back —
    # pad rows have length 0: they copy nothing and come back zero
    RB = 8
    Rp = -(-R // RB) * RB
    if Rp != R:
        pad = [(0, Rp - R), (0, 0)]
        idx = jnp.pad(idx, pad)
        wo = jnp.pad(wo, pad)
        wb = jnp.pad(wb, pad)
    # the lengths reach SMEM as a blocked operand, which Mosaic wants
    # in whole (8, 128) blocks: one block holds the lengths of 1024
    # rows and stays put for the 1024 // RB programs that share it
    Rl = -(-R // (8 * _LANES)) * (8 * _LANES)
    lengths = jnp.pad(jnp.clip(lengths.astype(jnp.int32), 0, C),
                      (0, Rl - R)).reshape(Rl // _LANES, _LANES)
    row_block = pl.BlockSpec((RB, C), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    A, b = pl.pallas_call(
        functools.partial(_gather_gram_kernel, RB=RB, C=C, T=T, kp=kp,
                          G=G, resident=resident),
        grid=(Rp // RB,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # idx: stays in HBM
            pl.BlockSpec((8, _LANES), lambda i: (i // (8 * _LANES // RB), 0),
                         memory_space=pltpu.SMEM),
            row_block,      # idx again, as a vector operand (slot mask)
            row_block,
            row_block,
            pl.BlockSpec(memory_space=pl.ANY),   # F lines: either
                                                 # fetch's HBM source
        ],
        out_specs=(
            pl.BlockSpec((RB, kp, kp), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((RB, kp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Rp, kp, kp), jnp.float32),
            jax.ShapeDtypeStruct((Rp, kp), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.SMEM((2, RB, C), jnp.int32),
            pltpu.VMEM((2, T, L), jnp.float32),
            pltpu.VMEM((L, L), jnp.float32),
            pltpu.VMEM((1, L), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            # what a line is fetched through: the table, or the line
            # copies' semaphore
            (pltpu.VMEM((lines, L), jnp.float32) if resident
             else pltpu.SemaphoreType.DMA),
        ],
        # in order: a program takes the index block its predecessor
        # started, and the table the first one filled
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT if resident else None),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * C * L * (L + 1),
            # index twice and the weights; the table once, or a line a
            # slot; the results
            bytes_accessed=(16 * R * C
                            + 4 * L * (lines if resident else R * C)
                            + 4 * R * kp * (kp + 1)),
            transcendentals=0,
        ),
        name="gather_gram",
        interpret=interpret,
    )(idx, lengths, idx, wo, wb, F)
    return A[:R, :k, :k], b[:R, :k]


def resolve_gram_mode(platform: Optional[str] = None) -> str:
    """Resolve ``PIO_PALLAS_GRAM`` to the gather→Gram implementation for
    a trace that will run on ``platform``:

    - ``"pallas"`` — the fused kernel (:func:`gather_gram`);
    - ``"interpret"`` — the same kernel under the Mosaic interpreter
      (chip-free CPU parity testing of the TRAIN-level program);
    - ``"off"`` — the XLA gather + packed einsum path.

    The rule is the platform and the flag, nothing else: ``auto``
    (default, and any other spelling) is the kernel on a TPU and XLA
    elsewhere; ``0`` forces XLA everywhere; ``interpret`` is the tests'
    escape hatch. Nothing is tried and caught here — on a TPU a
    selected kernel compiles, or the train fails with the compiler's
    message (tests/test_chip_compile.py holds the kernel to the chip's
    compiler at every ladder width).
    """
    flag = os.environ.get("PIO_PALLAS_GRAM", "auto").strip().lower()
    if flag == "0":
        return "off"
    if flag == "interpret":
        return "interpret"
    from predictionio_tpu import ops

    return "pallas" if ops.use_pallas(platform) else "off"
