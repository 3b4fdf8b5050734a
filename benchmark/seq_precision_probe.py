#!/usr/bin/env python3
"""By hand, on the chip: what the comparison of the sequence cell reads
when EVERYTHING is computed in bfloat16 — the nearest precision below
the one the configuration states (bfloat16 operands, float32
accumulation, router, softmax, norms and loss). Each limit of the
configuration's ``reference`` must lie under these readings and over
the program's own (``run.py`` prints those).

    python3 benchmark/seq_precision_probe.py --seed <n> [--tiny]

The reference in bfloat16 against the reference in float32, on the
first batch of the seed's histories (packed from the generator's ids,
no store): the first step's losses and per-group gradient norms on the
seeded initial weights, and both heads' logits of the first sequences
on the weights ONE train of the program leaves (what the cell loads
back) — beside the program's own logits there.
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
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import seqdata
    from predictionio_tpu.models import glm4_moe_lite as glm

    gen = harness.load_module("generators", "seq_train_jobs")
    with open(os.path.join(BENCH, "configs",
                           "seqrec-glm47flash-ep8.json")) as f:
        config = json.load(f)
    shape = config["sample"] if args.tiny else config
    cfg = glm.GlmConfig.from_architecture(gen.architecture(config, shape))
    say(f"device: {harness.device_report()}")
    data = seqdata.Histories(shape, args.seed)
    histories = [h + 1 for h in data.histories()]
    packed = glm.pack_histories(histories, cfg.seq_len, cfg.seqs_per_step,
                                args.seed % (1 << 31))
    n = int(config["reference"]["sequences_compared"])

    exact, lower = gen.Reference(cfg), gen.Reference(cfg, jnp.bfloat16)
    want = gen.reference_first_step(exact, args.seed, packed)
    low = gen.reference_first_step(lower, args.seed, packed)
    say(f"loss: float32 {want[0]:.6f}, bfloat16 {low[0]:.6f}: "
        f"|diff| {abs(want[0] - low[0]):.3e}")
    say(f"MTP loss: float32 {want[1]:.6f}, bfloat16 {low[1]:.6f}: "
        f"|diff| {abs(want[1] - low[1]):.3e}")
    worst = 0.0
    for group in sorted(want[2]):
        rel = abs(low[2][group] - want[2][group]) / max(want[2][group], 1e-30)
        worst = max(worst, rel)
        say(f"gradient norm of {group}: float32 {want[2][group]:.5e}, "
            f"bfloat16 {low[2][group]:.5e}: relative difference {rel:.3e}")
    say(f"worst relative difference of a group's gradient norm: {worst:.3e}")

    import jax

    host, losses = glm.glm_train(histories, cfg, shape["train"]["epochs"],
                                 shape["train"]["lr"],
                                 args.seed % (1 << 31))
    say(f"one train of the program: loss {losses[0]:.4f} -> "
        f"{losses[-4:].mean():.4f}")
    model = jax.device_put(host)
    ref32 = gen.reference_logits(exact, model, packed, n)
    ref16 = gen.reference_logits(lower, model, packed, n)
    for head, a, b in zip(("next-item", "MTP"), ref16, ref32):
        say(f"{head} logits, bfloat16 against float32: "
            f"{gen.compare_logits(a, b)}")
    import numpy as np

    program = [np.asarray(g) for g in glm.sequence_logits(model, {
        k: jnp.asarray(getattr(packed, k)[:n]) for k in glm.BATCH_KEYS}, cfg)]
    for head, a, b in zip(("next-item", "MTP"), program, ref32):
        say(f"{head} logits, the PROGRAM against float32: "
            f"{gen.compare_logits(a, b)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
