"""Traffic kind ``smallthinker_train_jobs``: ``lfm2_train_jobs`` (whole
warm ``pio train`` verbs of the ``sequentialrec`` template back to back,
for ANY block-stack backbone of the template's table, with its
``correct`` and its comparison against ``reference/<model_type>_jnp.py``)
with the ``smallthinker`` backbone's needs entered in its ``ROOFLINES``.

That table is a literal of ``lfm2_train_jobs.py`` and a PR that adds a
cell may edit no benchmark file that is there, so the entry is made on
the LOADED module. Nothing is copied: parameters, phases, checks and
the last line are that generator's.
"""

from __future__ import annotations

# the model FIRST: a tree without the backbone fails here, in seconds,
# before any data is made or a store imported
from predictionio_tpu.models import seq_backbone

seq_backbone.backbone("smallthinker")

from harness import load_module  # noqa: E402

shared = load_module("generators", "lfm2_train_jobs")
shared.ROOFLINES["smallthinker"] = "roofline_smallthinker"
run = shared.run
