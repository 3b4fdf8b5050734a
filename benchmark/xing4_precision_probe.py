#!/usr/bin/env python3
"""By hand, on the chip: what the comparison of the ``xing4_0`` cell
(``generators/xing4_train_jobs.py``) reads when the reference is WRONG —
each limit of the configuration's ``reference`` must lie under these
readings and over the program's own (``run.py`` prints those):

- everything computed in bfloat16 — the nearest precision below the one
  the configuration states (bfloat16 operands, float32 accumulation,
  stream, coefficients, Sinkhorn chain, router, softmax, norms, loss);
- one wrong equation planted (``reference/xing4_0_jnp.FAULT``): H_res
  used as its transpose; H_post without its factor 2; the softmax scale
  without YaRN's mscale².

    python3 benchmark/xing4_precision_probe.py --seed <n> [--tiny]

``lfm2_precision_probe.py``'s method: each wrong reference against the
float32 one on the first batch of the seed's histories (packed from the
generator's ids, no store) — (a) the first step's loss and per-group
gradient norms on the seeded initial weights, (b) the logits of the
first sequences on the weights ONE train of the program leaves (what the
cell loads back) — beside the program's own logits there — and (c) the
generator's own check of ONE sublayer's mixer on seeded operands
(``xing4_train_jobs.check_mixer``), where a wrong mixer shows that (a)
and (b) are nearly blind to at the coefficients' start. A fault moves
the reference exactly as it would move a program that had it: the
comparison is of two numbers, whichever side is wrong.
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

CONFIG = "seqrec-xing4-29b-a4b-ep8"
FAULTS = ("res_transposed", "post_unscaled", "plain_scale")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import seqdata
    from predictionio_tpu.models import seq_backbone
    from reference import xing4_0_jnp as ref

    own = harness.load_module("generators", "xing4_train_jobs")
    gen = own.shared
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
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

    # (c) first: it needs no model
    x = {k: jnp.asarray(v) for k, v in own.mixer_operands(
        cfg, args.seed).items()}
    mixed = own.mixer_reference(cfg)(x)
    say(f"(c) the PROGRAM's mixer against float32: "
        f"{own.mixer_rel_rms(own.mixer_program(cfg)(x), mixed):.3e}")
    say(f"(c) bfloat16: "
        f"{own.mixer_rel_rms(own.mixer_reference(cfg, jnp.bfloat16)(x), mixed):.3e}")
    for name in FAULTS:
        ref.FAULT = name            # read when the function is traced
        say(f"(c) {name}: "
            f"{own.mixer_rel_rms(own.mixer_reference(cfg)(x), mixed):.3e}")
        ref.FAULT = None
    del x, mixed

    def wrong(name):
        """A reference traced with the fault (or the dtype) in place."""
        if name == "bfloat16":
            return gen.Reference(backbone, cfg, jnp.bfloat16)
        ref.FAULT = name            # read when the program is traced
        return gen.Reference(backbone, cfg)

    def first_step(name):
        made = wrong(name)
        out = gen.reference_first_step(made, args.seed, packed)
        ref.FAULT = None
        return made, out

    exact, want = first_step(None)
    made = {}
    for name in ("bfloat16",) + FAULTS:
        made[name], got = first_step(name)
        diff = abs(got[0][0] - want[0][0])
        worst, group = max(
            (abs(got[1][g] - want[1][g]) / max(want[1][g], 1e-30), g)
            for g in want[1])
        say(f"(a) {name}: loss {got[0][0]:.6f} against {want[0][0]:.6f}: "
            f"|diff| {diff:.3e}; worst relative difference of a group's "
            f"gradient norm {worst:.3e} ({group})")

    host, losses = backbone.train(histories, cfg, shape["train"]["epochs"],
                                  shape["train"]["lr"],
                                  args.seed % (1 << 31))
    say(f"one train of the program: loss {losses[0]:.4f} -> "
        f"{losses[-4:].mean():.4f}")
    model = jax.device_put(host)
    ref32 = gen.reference_logits(exact, model, packed, n)[0]
    program = np.asarray(backbone.sequence_logits(model, {
        k: jnp.asarray(getattr(packed, k)[:n])
        for k in backbone.batch_keys}, cfg)[0])
    say(f"(b) the PROGRAM against float32: "
        f"{gen.shared.compare_logits(program, ref32)}")
    for name in ("bfloat16",) + FAULTS:
        # each was traced with its fault in place; the switch is off now
        low = gen.reference_logits(made[name], model, packed, n)[0]
        say(f"(b) {name} against float32: "
            f"{gen.shared.compare_logits(low, ref32)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
