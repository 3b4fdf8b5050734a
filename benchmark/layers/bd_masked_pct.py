"""Layer "objective": the rows the steps' noise masked over the real
events the steps saw, in percent (the ``seqrec.fit`` span's counters
``bd_masked`` ÷ ``bd_real``): 50 by the schedule p ~ U(ε, 1); None
where the program masks nothing."""

import spans


def read(obs):
    tree = spans.tree_of(obs)
    masked = spans.attr_of(tree, "seqrec.fit", "bd_masked")
    real = spans.attr_of(tree, "seqrec.fit", "bd_real")
    return None if not real or masked is None else 100.0 * masked / real
