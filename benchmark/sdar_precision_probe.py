#!/usr/bin/env python3
"""By hand, on the chip: what the comparison of the ``sdar_moe`` cell
(``generators/sdar_train_jobs.py``) reads when EVERYTHING is computed in
bfloat16 — the nearest precision below the one the configuration
states. Each limit of the configuration's ``reference`` must lie under
these readings and over the program's own (``run.py`` prints those).

    python3 benchmark/sdar_precision_probe.py --seed <n> [--tiny]
                                              [--lower 0]

``lfm2_precision_probe.py``'s method with the generator's own pieces
(its ``Reference`` with the weighted loss, ``reference_first_step``,
``reference_logits``, ``compare_logits``) on the seed's histories and
the FIRST step's noise, drawn by the program's noise function: the
reference in bfloat16 against itself in float32 — the first step's
loss and per-group gradient norms on the seeded initial weights, and
the noised stream's logits of the first sequences on the weights ONE
train of the program leaves — beside the program's own first loss and
logits there. ``--lower 0`` leaves the bfloat16 reference out: the
program alone against the float32 reference (what a scratch copy with
a planted fault reads).
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
    ap.add_argument("--lower", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import seqdata
    from predictionio_tpu.models import seq_backbone

    sdar = harness.load_module("generators", "sdar_train_jobs")
    gen = sdar.shared
    with open(os.path.join(BENCH, "configs",
                           "seqrec-sdar-30b-a3b-ep8.json")) as f:
        config = json.load(f)
    shape = config["sample"] if args.tiny else config
    backbone = gen.backbone_of(config)
    cfg = backbone.config.from_architecture(gen.architecture(config, shape))
    say(f"device: {harness.device_report()}")
    seed = args.seed % (1 << 31)
    histories = [h + 1 for h in seqdata.Histories(shape,
                                                  args.seed).histories()]
    packed = seq_backbone.pack_histories(histories, cfg.seq_len,
                                         cfg.seqs_per_step, seed)
    packed = sdar.streams(packed, cfg, seed,
                          config["reference"]["early_positions"])
    say("the first step's weights average "
        f"{packed.weight[:cfg.seqs_per_step].mean():.4f} over its events")
    n = int(config["reference"]["sequences_compared"])

    exact = gen.Reference(backbone, cfg)
    want = gen.reference_first_step(exact, args.seed, packed)
    say(f"loss: float32 {want[0][0]:.6f}")
    if args.lower:
        lower = gen.Reference(backbone, cfg, jnp.bfloat16)
        low = gen.reference_first_step(lower, args.seed, packed)
        say(f"loss: bfloat16 {low[0][0]:.6f}: "
            f"|diff| {abs(want[0][0] - low[0][0]):.3e}")
        worst = 0.0
        for group in sorted(want[1]):
            rel = abs(low[1][group] - want[1][group]) / max(
                want[1][group], 1e-30)
            worst = max(worst, rel)
            say(f"gradient norm of {group}: float32 {want[1][group]:.5e}, "
                f"bfloat16 {low[1][group]:.5e}: relative difference "
                f"{rel:.3e}")
        say("worst relative difference of a group's gradient norm: "
            f"{worst:.3e}")

    host, losses = backbone.train(histories, cfg, shape["train"]["epochs"],
                                  shape["train"]["lr"], seed)
    say(f"one train of the program: loss {losses[0]:.6f} -> "
        f"{losses[-4:].mean():.4f}; |first loss - float32 reference| "
        f"{abs(float(losses[0]) - want[0][0]):.3e}")
    model = jax.device_put(host)
    ref32 = gen.reference_logits(exact, model, packed, n)[0]
    program = np.asarray(backbone.sequence_logits(model, {
        k: jnp.asarray(getattr(packed, k)[:n])
        for k in backbone.batch_keys}, cfg)[0])
    if args.lower:
        ref16 = gen.reference_logits(lower, model, packed, n)[0]
        say("the noised stream's logits, bfloat16 against float32: "
            f"{gen.shared.compare_logits(ref16, ref32)}")
    say("the noised stream's logits, the PROGRAM against float32: "
        f"{gen.shared.compare_logits(program, ref32)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
