"""``data/pipeline.py interactions_from_columnar`` as it stood before
ISSUE 30 (three ``[keep]`` copies of every column, two
``np.unique(return_index=True)`` over the kept codes, two ``remap``
gathers), kept as the ORACLE the O(n) index build is held to, id for
id and array for array (``tests/test_read_index.py``). The body is
the parent's, unchanged but for the function's name."""

from typing import Any, Dict, List, Optional

import numpy as np

from predictionio_tpu.data.pipeline import ColumnarEvents, InteractionData
from predictionio_tpu.utils.bimap import BiMap


def interactions_from_columnar_oracle(
    cols: ColumnarEvents,
    value_spec: Optional[Dict[str, Any]] = None,
    default_spec: Any = 1.0,
    chunk_size: int = 65536,
) -> InteractionData:
    """Vectorized :class:`InteractionData` from a columnar scan.

    ``value_spec`` maps event name → ``"prop"`` (use the scan's
    extracted numeric property; non-finite drops the event, mirroring
    the generic path's ``value_fn → None``) or a float constant.
    Unlisted names take ``default_spec``. Vocabularies are re-densified
    to kept events only (first-seen order), so the result is
    indistinguishable from :func:`read_interactions` over ``find()``.
    """
    # per-NAME lookup arrays, then one gather over name_idx — O(n),
    # independent of how many distinct event names the log holds
    specs = [(value_spec or {}).get(name, default_spec)
             for name in cols.names]
    is_prop = np.asarray([s == "prop" for s in specs], bool)
    consts = np.asarray([1.0 if s == "prop" else float(s) for s in specs],
                        np.float64)
    prop_row = is_prop[cols.name_idx]
    vals = np.where(prop_row, cols.values, consts[cols.name_idx])
    keep = ~prop_row | np.isfinite(cols.values)

    def densify(idx_arr: np.ndarray, table: List[str]):
        """Trim the vocab to kept events, preserving first-seen order."""
        uniq, first_pos = np.unique(idx_arr, return_index=True)
        order = np.argsort(first_pos, kind="stable")
        uniq = uniq[order]
        remap = np.full(len(table), -1, np.int32)
        remap[uniq] = np.arange(len(uniq), dtype=np.int32)
        ids = [table[int(u)] for u in uniq]
        return remap, BiMap({s: i for i, s in enumerate(ids)})

    ent_kept = cols.entity_idx[keep]
    tgt_kept = cols.target_idx[keep]
    v_kept = vals[keep].astype(np.float32)
    remap_e, user_ids = densify(ent_kept, cols.entity_ids)
    remap_t, item_ids = densify(tgt_kept, cols.target_ids)
    uu = remap_e[ent_kept]
    ii = remap_t[tgt_kept]
    n_events = int(uu.shape[0])

    def chunk_factory():
        for s in range(0, max(n_events, 1), chunk_size):
            if s >= n_events:
                return
            yield (uu[s:s + chunk_size], ii[s:s + chunk_size],
                   v_kept[s:s + chunk_size])

    return InteractionData(user_ids, item_ids, chunk_factory, n_events)
