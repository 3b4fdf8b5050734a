"""Layer "training read": seconds of the program's ``storage.scan.load``
span in the traced train — ``data/snapshot.load_snapshot`` alone: the
snapshot's file opened, every section digested against the manifest and
the columns built. ``read_scan_s`` less this is what the two
``creation_stats`` probes and the delta scan cost (``storage.scan.probe``).
The span's counters ``bytes`` (digested), ``copied_bytes`` (written into
fresh memory for the columns: 0 when they are views of the mapped file),
``mapped`` and ``schema`` are on the ``train spans:`` lines of the run.
None on a program that has no such span."""

import spans


def read(obs):
    return spans.seconds_of(spans.tree_of(obs), "storage.scan.load")
