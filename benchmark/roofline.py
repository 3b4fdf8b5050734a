"""What a kernel's call needs, from shapes: operations and bytes, and
the least time a chip with the given peaks could take for them.

Kept with the benchmark so that no PR that claims a gain can change
the yardstick. (``train_flops`` / ``train_bytes`` copied from
``bench.py _train_flops`` / ``_train_bytes``.)
"""

from __future__ import annotations

#: the fused gather→Gram kernel fetches one 128-lane float32 line per
#: gathered row, whatever the rank (ops/gram.py, PR 22)
LINE_BYTES = 512
#: per gathered row besides the line: the int32 index and the two
#: float32 weights the kernel streams
ROW_SIDE_BYTES = 4 + 4 + 4


def kernel_takes_bucket(bucket) -> bool:
    """The shape rule by which ``_make_half`` hands a bucket — segmented
    or not — to ``gather_gram`` when the Gram mode is fused: a width of
    whole 128-lane tiles (``ops/gram.kernel_takes_width``)."""
    return bucket.C % 128 == 0


def gather_gram_need(prep, rank: int, iterations: int) -> dict:
    """Bytes and operations ``gather_gram`` needs in ONE train of
    ``iterations`` iterations on the prepared layout: for every real
    (unpadded) interaction of a bucket the kernel takes, one line, its
    index and weights, and k·(k+1) multiply-adds; for every row, the
    (k, k+1) normal equations written back."""
    real = rows = padded = 0
    for side in (prep.u_side, prep.i_side):
        for b in side.buckets:
            if kernel_takes_bucket(b):
                real += int(b.mask.sum())
                rows += b.n_slabs * b.slab
                padded += b.n_slabs * b.slab * b.C
    return {
        "bytes": iterations * (real * (LINE_BYTES + ROW_SIDE_BYTES)
                               + rows * rank * (rank + 1) * 4),
        "flops": iterations * real * 2 * rank * (rank + 1),
        "real_rows": real, "padded_rows": padded, "bucket_rows": rows,
    }


def least_seconds(need: dict, peaks: dict):
    """(seconds, which bound): the larger of operations over the peak
    rate and bytes over the peak bandwidth."""
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = need["flops"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops,
                                                             "flops")


def train_flops(nnz: int, n_users: int, n_items: int, rank: int,
                iterations: int) -> float:
    """Operations the ALS mathematics needs: per interaction one k×k
    rank-one update and one k-vector update on each side, per entity
    one Cholesky solve (k³/3 + 2k²)."""
    gram = 2.0 * nnz * 2 * rank * (rank + 1)
    solve = (n_users + n_items) * (rank ** 3 / 3.0 + 2.0 * rank ** 2)
    return iterations * (gram + solve)


def train_bytes(nnz: int, n_users: int, n_items: int, rank: int,
                iterations: int) -> float:
    """Bytes the ALS mathematics must move: per interaction one factor
    row, index and value on each side; per entity its factor row
    written."""
    gather = 2.0 * nnz * (rank * 4 + 4 + 4)
    write = (n_users + n_items) * rank * 4
    return iterations * (gather + write)
