"""The layout builder ``models/als.py _bucket_side`` as it stood before
ISSUE 28 (comparison sort, int64 ``within``/``row``/``col``, two-index
scatters, ``bincount`` dense head), kept as the ORACLE the O(nnz)
builder is held to, array for array (``tests/test_als.py
TestBucketedLayout::test_layout_equals_oracle``). The body is the
parent's, unchanged but for the ``als.`` prefix on the module's names
(read at call time, so a test's ``monkeypatch`` of ``_LADDER``,
``_C_MAX`` or ``_SLAB_ELEMS`` reaches both builders)."""

import numpy as np

import predictionio_tpu.models.als as als


def bucket_side_oracle(idx_self, idx_other_pos, vals, n_self, counts,
                       perm, inv_perm, n_other=None,
                       bounds=None) -> als._BucketSide:
    """Bucket one orientation. ``idx_other_pos`` must already be mapped
    to the other side's factor-row positions; ``counts/perm/inv_perm``
    come from :func:`_perm_by_count_desc` on this side's counts;
    ``n_other`` is the other side's factor-row count (the width of
    dense-head weight rows — the gathered factor matrix height).

    ``bounds`` forces common bucket boundaries (sharded path: the
    max-merge over all devices, so every device traces one program).
    Forced boundaries are safe: the entity at permuted position p has
    count ≤ every entity before it, and merged boundaries only ever
    move p into the dense head or a bucket at least as wide as its
    natural one — so capacity C ≥ count always holds.

    Invariant the fused kernel rests on: in every bucket row — regular
    or segmented, natural or forced boundaries — the real slots are a
    PREFIX (``col = within`` resp. ``within % C``; only an entity's
    last segment row is short), so ``mask.sum(1)`` is the row's real
    length and ``ops.gather_gram`` fetches just that many lines
    (tests/test_als.py holds it).
    """
    if n_other is None:
        n_other = (int(idx_other_pos.max()) + 1 if idx_other_pos.size
                   else 1)
    nnz = idx_self.shape[0]
    pos = inv_perm[idx_self]
    order = np.argsort(pos, kind="stable")
    ps, o, v = pos[order], idx_other_pos[order], vals[order]
    counts_perm = counts[perm].astype(np.int64)
    starts = np.zeros(n_self + 1, np.int64)
    np.cumsum(counts_perm, out=starts[1:])
    within = (np.arange(nnz, dtype=np.int64) - starts[ps]).astype(np.int64)

    if bounds is None:
        bounds = als._merge_bounds([counts_perm], n_other)
    nb_dense, (nb_seg, rows_cap), regs = bounds

    # dense head: heaviest entities (permuted positions [0, nb_dense))
    # as dense weight rows — see _DENSE_RATIO
    dense = None
    if nb_dense:
        hi = int(starts[min(nb_dense, n_self)])
        # bincount over linearized (entity, other) indices: np.add.at
        # is an unbuffered scalar scatter, ~50-100× slower over the
        # millions of nnz the dense head holds
        lin = ps[:hi].astype(np.int64) * n_other + o[:hi]
        size = nb_dense * n_other
        w_cnt = np.bincount(lin, minlength=size).astype(
            np.float32).reshape(nb_dense, n_other)
        w_val = np.bincount(lin, weights=v[:hi], minlength=size).astype(
            np.float32).reshape(nb_dense, n_other)
        cnts = np.zeros(nb_dense, np.float32)
        real = min(nb_dense, n_self)
        cnts[:real] = counts_perm[:real]
        dense = als._DenseHead(nb_dense, n_other, w_cnt, w_val, cnts)
        # rebase the remainder so the seg/ladder code below sees a
        # self-contained problem over positions [nb_dense, n_self)
        ps = ps[hi:] - nb_dense
        o, v, within = o[hi:], v[hi:], within[hi:]
        counts_perm = counts_perm[nb_dense:]
        starts = starts[nb_dense:] - hi
        n_self_rest = max(n_self - nb_dense, 0)
    else:
        n_self_rest = n_self
    buckets = []

    # heavy entities (count > als._C_MAX): one SEGMENTED bucket — each
    # entity spans ceil(count/C) rows of width C; the one-hot ``seg``
    # matrix aggregates row partials per entity inside the compiled
    # program. Entities are count-descending, so these are the first
    # positions after the dense head and the output concatenation order
    # is preserved.
    if nb_seg:
        C = als._C_MAX
        cnts = counts_perm[:nb_seg]
        rows_per = (cnts + C - 1) // C  # forced-in light entities: 1 row
        row_starts = np.zeros(nb_seg + 1, np.int64)
        np.cumsum(rows_per, out=row_starts[1:])
        n_rows = int(row_starts[-1])
        # slab capped at the (merged) row count: padding a small bucket
        # to a full 64MB slab made every tiny block solve tens of
        # thousands of identity systems
        slab = max(1, min(als._SLAB_ELEMS // C, rows_cap))
        n_slabs = -(-rows_cap // slab)
        assert n_rows <= n_slabs * slab
        R = n_slabs * slab
        oi = np.zeros((R, C), np.int32)
        vv = np.zeros((R, C), np.float32)
        mm = np.zeros((R, C), np.float32)
        hi = int(starts[nb_seg])
        row = row_starts[ps[:hi]] + within[:hi] // C
        col = within[:hi] % C
        oi[row, col] = o[:hi]
        vv[row, col] = v[:hi]
        mm[row, col] = 1.0
        row_ent = np.repeat(np.arange(nb_seg), rows_per)
        # slab-local one-hot: entity index relative to the slab's first
        # entity (rows are entity-sorted → ≤ slab consecutive entities)
        if n_rows:
            seg_off = row_ent[np.minimum(np.arange(n_slabs) * slab,
                                         n_rows - 1)].astype(np.int32)
            local = row_ent - seg_off[np.arange(n_rows) // slab]
            seg = np.zeros((R, slab), np.float32)
            seg[np.arange(n_rows), local] = 1.0  # pad rows stay all-zero
        else:  # a device with no ratings in the (forced) seg range
            seg_off = np.zeros(n_slabs, np.int32)
            seg = np.zeros((R, slab), np.float32)
        buckets.append(als._Bucket(
            C, nb_seg, slab, n_slabs,
            oi.reshape(n_slabs, slab, C),
            vv.reshape(n_slabs, slab, C),
            mm.reshape(n_slabs, slab, C),
            cnts.astype(np.float32),
            seg=seg.reshape(n_slabs, slab, slab),
            seg_off=seg_off))

    # the rest: one row per entity, padded to the bucket width
    e = nb_seg
    for C, nb in regs:
        slab = max(1, min(als._SLAB_ELEMS // C, nb))
        n_slabs = -(-nb // slab)
        nb_pad = n_slabs * slab
        oi = np.zeros((nb_pad, C), np.int32)
        vv = np.zeros((nb_pad, C), np.float32)
        mm = np.zeros((nb_pad, C), np.float32)
        # forced boundaries may extend past this device's entities
        e_end = min(e + nb, n_self_rest)
        lo, hi = int(starts[min(e, n_self_rest)]), int(starts[e_end])
        row = (ps[lo:hi] - e).astype(np.int64)
        col = within[lo:hi]
        oi[row, col] = o[lo:hi]
        vv[row, col] = v[lo:hi]
        mm[row, col] = 1.0
        cnt = np.zeros(nb_pad, np.float32)
        cnt[: max(e_end - e, 0)] = counts_perm[e:e_end]
        buckets.append(als._Bucket(
            C, nb, slab, n_slabs,
            oi.reshape(n_slabs, slab, C),
            vv.reshape(n_slabs, slab, C),
            mm.reshape(n_slabs, slab, C),
            cnt.reshape(n_slabs, slab)))
        e += nb
    return als._BucketSide(n_self, perm, inv_perm, buckets, dense=dense)
