"""Layer "residual mixer": how far from doubly stochastic the Sinkhorn
chain leaves H_res — the largest |row or column sum − 1| over the tokens,
sublayers, layers and steps of the newest train (the ``seqrec.fit`` span's
counter ``mhc_ds_err``, the largest of the steps' records). 0 = exact; None
where the program counts none."""

import spans


def read(obs):
    return spans.attr_of(spans.tree_of(obs), "seqrec.fit", "mhc_ds_err")
