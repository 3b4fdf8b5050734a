"""Traffic kind ``sdar_train_jobs``: ``lfm2_train_jobs`` (whole warm
``pio train`` verbs of the ``sequentialrec`` template back to back, for
ANY block-stack backbone of the template's table, with its ``correct``
and its comparison against ``reference/<model_type>_jnp.py``) for the
``sdar_moe`` backbone, which trains by BLOCK DIFFUSION: its needs
entered in the loaded module's ``ROOFLINES``, and on that LOADED module
(a PR that adds a cell may edit no benchmark file that is there) only
what the objective changes —

- the batches made again carry the FIRST step's noise, drawn once more
  by the program's own noise function (``sdar_moe.first_noise``): the
  noised stream's tokens and each row's weight are the reference's
  INPUT;
- the reference's share of a step's loss is the WEIGHTED cross-entropy
  of the noised stream's rows against their own items
  (:class:`Reference`: ``_build``'s ``part``);
- the divisor is the step's real events (``TARGETS``: what
  ``reference_first_step`` counts);
- two checks MORE, of what the objective adds and the generator's own
  three limits cannot see (``check_reference``): the logits of the rows
  EARLY in their segment (``reference.early_positions``), where the
  block rule decides most of what a row sees — a noised row let see its
  own block's clean keys moves them by tens of percent and the median
  over ALL tokens by nothing (a late row has thousands of keys: four
  more are lost in them) —, and the mean of the first step's weights
  over its real events, 1 by E[mask / p] = 1.

``run``, the phases, every other check and the last line are that
generator's.
"""

from __future__ import annotations

import importlib
import types

import numpy as np

# the model FIRST: a tree without the backbone fails here, in seconds,
# before any data is made or a store imported
from predictionio_tpu.models import seq_backbone

seq_backbone.backbone("sdar_moe")

from predictionio_tpu.models import sdar_moe  # noqa: E402

from harness import load_module  # noqa: E402

shared = load_module("generators", "lfm2_train_jobs")
shared.ROOFLINES["sdar_moe"] = "roofline_sdar"
#: the head's divisor: every real event of the step, masked or not
shared.TARGETS["loss"] = "seg"

_pack = shared.shared.first_batches
_compare = shared.shared.compare_logits
_check = shared.check_reference
#: the batches the comparison runs on (``compare_logits`` is handed
#: logits only) and how many of a segment's first rows are "early"
_last = {}


def streams(packed, cfg, seed: int, early: int):
    """``packed`` with the noise of a train's FIRST step on every
    sequence (``noised``, ``weight``), drawn by the program's own noise
    function; remembered for :func:`compare_logits`."""
    noised, weight = sdar_moe.first_noise(packed, cfg, seed % (1 << 31))
    _last.update(early=int(early), batches=types.SimpleNamespace(
        **packed._asdict(), noised=noised, weight=weight))
    return _last["batches"]


def first_batches(cfg, seed: int, storage, item_ids):
    """The packed sequences a train of this store sees, with the noise
    of its FIRST step on every one of them."""
    return streams(_pack(cfg, seed, storage, item_ids), cfg, seed,
                   _last["early"])


def compare_logits(got, want) -> dict:
    """``seq_train_jobs.compare_logits`` and besides ``early_median``:
    the same median over the tokens among the first ``early_positions``
    of their segment only."""
    out = _compare(got, want)
    b = _last["batches"]
    n = got.shape[0]
    early = ((b.pos[:n] < _last["early"]) & (b.seg[:n] > 0)).reshape(-1)
    diff = np.sqrt(np.square(got.astype(np.float64) - want).sum(-1))
    scale = np.sqrt(np.mean(np.square(want, dtype=np.float64).sum(-1)))
    out["early_median"] = float(np.median(diff.reshape(-1)[early]) / scale)
    return out


def check_reference(verdict, config: dict, backbone, cfg, seed: int,
                    storage, fit: dict) -> dict:
    tol = config["reference"]
    _last["early"] = tol["early_positions"]
    out = _check(verdict, config, backbone, cfg, seed, storage, fit)
    early = out["loss"]["early_median"]
    verdict.check(early <= tol["logits_early_median_max"],
                  f"the logits of the first {tol['early_positions']} rows "
                  f"of each segment: median over tokens of |diff| / rms "
                  f"|logits| {early:.2e} <= "
                  f"{tol['logits_early_median_max']}")
    b, B = _last["batches"], cfg.seqs_per_step
    mean = float(b.weight[:B].sum() / max((b.seg[:B] > 0).sum(), 1))
    verdict.check(abs(mean - 1.0) <= tol["weight_mean_abs_max"],
                  f"the first step's weights average {mean:.4f} over its "
                  f"real events: |mean - 1| <= {tol['weight_mean_abs_max']}")
    return out


class Reference(shared.Reference):
    """``lfm2_train_jobs.Reference`` with the block-diffusion loss: the
    noised stream's logits against the rows' OWN items, each row by its
    weight."""

    def _build(self, *args):
        import jax
        import jax.numpy as jnp

        ref = importlib.import_module("reference.sdar_moe_jnp")
        rcfg, held = dict(self.cfg.__dict__), self.cfg.held
        kw = {} if self.dtype is None else {"dtype": self.dtype}

        def part(w, b, seq, scales):
            logits, _loads = ref.forward(w, b, seq, rcfg, held,
                                         jax.checkpoint, **kw)
            ce = ref.ce_sum(logits, seq["tokens"], seq["weight"])
            return scales[0] * ce, ([ce], [logits])

        def run(w, b, seq, scales, acc):
            with jax.default_matmul_precision("highest"):
                (_, out), g = jax.value_and_grad(part, has_aux=True)(
                    w, b, seq, scales)
            return out, jax.tree.map(jnp.add, acc, g)

        return jax.jit(run, donate_argnums=(4,)).lower(*args).compile(
            compiler_options=shared.shared.REFERENCE_COMPILER_OPTIONS)


shared.shared.first_batches = first_batches
shared.shared.compare_logits = compare_logits
shared.check_reference = check_reference
shared.Reference = Reference
run = shared.run
