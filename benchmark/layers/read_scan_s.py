"""Layer "training read": seconds of the program's ``storage.scan``
span in the traced train — the columnar scan through the snapshot cache
(``data/store._scan_with_cache``), without the index and array building
that ``read_training_s`` also holds. Its ``scan_cache`` attribute (hit,
hit:delta, miss:…) is on the ``train spans:`` lines of the run."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "storage.scan")
