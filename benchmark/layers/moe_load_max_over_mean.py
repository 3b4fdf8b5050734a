"""Layer "expert dispatch": the busiest held expert's routed tokens
over the held experts' mean, worst layer, mean over the steps of the
newest train (the ``seqrec.fit`` span's counter). 1 = even."""

import spans


def read(obs):
    return spans.attr_of(spans.tree_of(obs), "seqrec.fit",
                         "moe_load_max_over_mean")
