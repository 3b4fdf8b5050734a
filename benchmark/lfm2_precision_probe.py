#!/usr/bin/env python3
"""By hand, on the chip: what the comparison of a block-stack cell
(``generators/lfm2_train_jobs.py``) reads when EVERYTHING is computed in
bfloat16 — the nearest precision below the one the configuration
states (bfloat16 operands, float32 accumulation, router, softmax,
norms, taps, gates and loss). Each limit of the configuration's
``reference`` must lie under these readings and over the program's own
(``run.py`` prints those).

    python3 benchmark/lfm2_precision_probe.py --seed <n> [--tiny]
                                              [--config <name>]

``seq_precision_probe.py``'s method for any backbone of the template's
table: the backbone's reference in bfloat16 against itself in float32,
on the first batch of the seed's histories (packed from the
generator's ids, no store): the first step's loss of each head and
per-group gradient norms on the seeded initial weights, and each
head's logits of the first sequences on the weights ONE train of the
program leaves (what the cell loads back) — beside the program's own
logits there.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import harness                                        # noqa: E402
from harness import say                               # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--config", default="seqrec-lfm2-8b-a1b-ep4")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import seqdata
    from predictionio_tpu.models import seq_backbone

    gen = harness.load_module("generators", "lfm2_train_jobs")
    with open(os.path.join(BENCH, "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    shape = config["sample"] if args.tiny else config
    backbone = gen.backbone_of(config)
    cfg = backbone.config.from_architecture(gen.architecture(config, shape))
    say(f"device: {harness.device_report()}")
    data = seqdata.Histories(shape, args.seed)
    histories = [h + 1 for h in data.histories()]
    packed = seq_backbone.pack_histories(
        histories, cfg.seq_len, cfg.seqs_per_step, args.seed % (1 << 31))
    n = int(config["reference"]["sequences_compared"])

    exact = gen.Reference(backbone, cfg)
    lower = gen.Reference(backbone, cfg, jnp.bfloat16)
    want = gen.reference_first_step(exact, args.seed, packed)
    low = gen.reference_first_step(lower, args.seed, packed)
    for head, a, b in zip(backbone.heads, want[0], low[0]):
        say(f"{head}: float32 {a:.6f}, bfloat16 {b:.6f}: "
            f"|diff| {abs(a - b):.3e}")
    worst = 0.0
    for group in sorted(want[1]):
        rel = abs(low[1][group] - want[1][group]) / max(want[1][group],
                                                        1e-30)
        worst = max(worst, rel)
        say(f"gradient norm of {group}: float32 {want[1][group]:.5e}, "
            f"bfloat16 {low[1][group]:.5e}: relative difference {rel:.3e}")
    say(f"worst relative difference of a group's gradient norm: {worst:.3e}")

    host, losses = backbone.train(histories, cfg, shape["train"]["epochs"],
                                  shape["train"]["lr"],
                                  args.seed % (1 << 31))
    say(f"one train of the program: loss {losses[0]:.4f} -> "
        f"{losses[-4:].mean():.4f}")
    model = jax.device_put(host)
    ref32 = gen.reference_logits(exact, model, packed, n)
    ref16 = gen.reference_logits(lower, model, packed, n)
    program = [np.asarray(g) for g in backbone.sequence_logits(model, {
        k: jnp.asarray(getattr(packed, k)[:n])
        for k in backbone.batch_keys}, cfg)]
    for head, low16, got, want32 in zip(backbone.heads, ref16, program,
                                        ref32):
        say(f"the {head} head's logits, bfloat16 against float32: "
            f"{gen.shared.compare_logits(low16, want32)}")
        say(f"the {head} head's logits, the PROGRAM against float32: "
            f"{gen.shared.compare_logits(got, want32)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
