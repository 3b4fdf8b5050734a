"""TPU kernels and compute primitives for the hot ops.

The reference delegates its hot math to Spark MLlib → netlib BLAS
(SURVEY.md §2b); here the equivalents are XLA programs plus hand-written
Pallas TPU kernels for the ops where fusion/streaming matters:

- :mod:`.gram` — batched weighted Gram accumulation (the ALS inner op).
- :mod:`.topk` — streaming score+top-k over item tiles (serving path).
- :mod:`.segment` — segment reductions (Naive Bayes, CCO counts).
- :mod:`.seq_attention` — causal attention inside the segments of a
  packed sequence, only the tiles a segment reaches (sequence backbone).
- :mod:`.gated_delta` — the gated delta rule (a linear-attention
  layer's recurrent state) along those segments, chunk by chunk: the
  chunks' triangular systems by their inverses, the walk over the
  chunks as a pair of Pallas kernels (forward, reverse) that keep the
  state in VMEM — plain ``jax.numpy`` and a ``lax.scan`` at shapes the
  kernels do not take (sequence backbone).
- :mod:`.hyper_connections` — a residual stream of n mixed copies: a
  sublayer's coefficients (one projection, two sigmoids, a Sinkhorn
  chain a token) and the stream's read and write-back as unrolled
  multiply-adds, in plain ``jax.numpy`` (sequence backbone).

The kernels above the last have an XLA twin; ``use_pallas()`` decides
by platform (compiled on TPU, XLA elsewhere, interpret-mode in tests)
— by rule, never by trying the kernel and catching its failure.
:mod:`.seq_attention` has no twin: compiled for a TPU, interpreted
anywhere else, decided where the program is lowered.
"""

from predictionio_tpu.ops.gram import (gather_gram, gather_gram_xla,
                                       resolve_gram_mode)
from predictionio_tpu.ops.segment import segment_count, segment_mean, segment_sum
from predictionio_tpu.ops.topk import (adc_scores, adc_shortlist,
                                       merge_shortlists, rerank_partial,
                                       rerank_topk, score_topk,
                                       score_topk_xla)


def use_pallas(platform=None) -> bool:
    """Compiled Pallas kernels only make sense on real TPU backends.

    ``platform`` is the platform the trace will actually run on (pass
    the mesh's / target device's ``.platform``); when None the default
    backend decides — callers compiling for an explicit device or mesh
    must pass it, because ``jax.default_backend()`` can differ from the
    execution platform (a CPU mesh on a host that also has a TPU, or a
    compile for a described, unattached chip).
    """
    if platform is None:
        import jax

        platform = jax.default_backend()
    return platform == "tpu"


__all__ = [
    "adc_scores", "adc_shortlist", "gather_gram", "gather_gram_xla",
    "merge_shortlists", "rerank_partial", "rerank_topk",
    "resolve_gram_mode", "score_topk", "score_topk_xla",
    "segment_sum", "segment_count", "segment_mean", "use_pallas",
]
