"""Traffic kind ``qwen3next_train_jobs``: ``lfm2_train_jobs`` (whole warm
``pio train`` verbs of the ``sequentialrec`` template back to back, for
ANY block-stack backbone of the template's table, with its ``correct``
and its comparison against ``reference/<model_type>_jnp.py``) for the
``qwen3_next`` backbone, three of whose four layers carry a RECURRENT
state along each segment: its needs entered in the loaded module's
``ROOFLINES``, and on that LOADED module (a PR that adds a cell may edit
no benchmark file that is there) three checks MORE, of what the
recurrence adds and the generator's own three limits cannot see
(``check_reference``):

- the loaded logits' median AS A SHARE of what the reference itself
  reads when it computes everything in bfloat16 — the nearest precision
  below the stated one — on the same loaded model and sequence
  (``reference.logits_vs_lower_max``). This block's median follows
  the seed's weights and the compared sequence's composition by a
  factor of 1.6 (4.4e-3 to 8.5e-3 over 32 pairs of them), the lower
  precision's alike (9.5e-3 to 1.39e-2): the ranges nearly meet, so no
  fixed limit lies between them with room, while their quotient stays
  within 0.46 to 0.58 (the configuration's ``reference.why``);

- the logits of the rows FIRST in their segment
  (``reference.early_positions``), where the reset decides what a row
  reads. Under the family's initialiser (``A_log`` = log U(0, 16),
  ``dt_bias`` 1) a row's state fades by e^−1.3·A: in all but a few
  heads nothing of it is left three rows on, so a program that did NOT
  zero the state at a segment's first row moves that row and the next
  and the median over ALL tokens by nothing;
- the SCAN itself at the timed shapes on seeded operands whose decay is
  SLOW (g = −U(0, ``scan_g_max``) a row: the state lives for hundreds
  of rows, through many chunks) over the first packed sequence's
  segments, against the reference's row-by-row recurrence in float32:
  the WORST row's |difference| ÷ rms |reference output|
  (``scan_row_worst_max``; the median is printed beside it). The
  trained model cannot show this path — at its initialiser the state
  that crosses a chunk's end is e^−80 of itself — and a state kept in
  bfloat16 shows here and nowhere else; one carried over a segment's
  start shows in the rows behind that start only (a few hundred of
  16,384: the median over rows sleeps, the worst row reads 1).

``run``, the phases, every other check and the last line are that
generator's.
"""

from __future__ import annotations

import numpy as np

# the model FIRST: a tree without the backbone fails here, in seconds,
# before any data is made or a store imported
from predictionio_tpu.models import seq_backbone

seq_backbone.backbone("qwen3_next")

from predictionio_tpu.ops import gated_delta  # noqa: E402

from harness import load_module, say  # noqa: E402
from reference import qwen3_next_jnp as ref  # noqa: E402

shared = load_module("generators", "lfm2_train_jobs")
shared.ROOFLINES["qwen3_next"] = "roofline_qwen3next"

_pack = shared.shared.first_batches
_compare = shared.shared.compare_logits
_check = shared.check_reference
#: the batches the comparison runs on (``compare_logits`` is handed
#: logits only) and how many of a segment's first rows are "early"
_last = {}


def first_batches(cfg, seed: int, storage, item_ids):
    """The packed sequences a train of this store sees, remembered for
    :func:`compare_logits` and :func:`check_scan`."""
    _last["batches"] = _pack(cfg, seed, storage, item_ids)
    return _last["batches"]


def compare_logits(got, want) -> dict:
    """``seq_train_jobs.compare_logits`` and besides ``early_median``:
    the same median over the tokens among the first ``early_positions``
    of their segment only."""
    out = _compare(got, want)
    b, n = _last["batches"], got.shape[0]
    early = ((b.pos[:n] < _last["early"]) & (b.seg[:n] > 0)).reshape(-1)
    diff = np.sqrt(np.square(got.astype(np.float64) - want).sum(-1))
    scale = np.sqrt(np.mean(np.square(want, dtype=np.float64).sum(-1)))
    out["early_median"] = float(np.median(diff.reshape(-1)[early]) / scale)
    _last["want"] = want
    return out


def scan_operands(cfg, seed: int, g_max: float) -> dict:
    """Seeded operands of the scan at the configuration's shapes, one
    sequence: unit keys and queries (the queries ÷ √d_k), normal
    values, β ~ U(0, 1), g = −U(0, ``g_max``)."""
    rng = np.random.default_rng([int(seed), 45])
    S, Hk, Hv = (cfg.seq_len, cfg.linear_num_key_heads,
                 cfg.linear_num_value_heads)
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim

    def unit(shape):
        x = rng.standard_normal(shape, np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    return {"q": unit((S, Hk, dk)) / np.float32(np.sqrt(dk)),
            "k": unit((S, Hk, dk)),
            "v": rng.standard_normal((S, Hv, dv), np.float32),
            "g": -rng.uniform(0.0, g_max, (S, Hv)).astype(np.float32),
            "beta": rng.uniform(0.0, 1.0, (S, Hv)).astype(np.float32)}


def check_scan(verdict, tol: dict, cfg, seed: int, seg) -> float:
    """The program's scan against the reference's recurrence on
    :func:`scan_operands` over the segments ``seg`` [S]: the worst
    row's |difference| over the rms of the reference's rows."""
    import jax
    import jax.numpy as jnp

    x = {k: jnp.asarray(v) for k, v in scan_operands(
        cfg, seed, tol["scan_g_max"]).items()}
    seg = jnp.asarray(seg)
    r = cfg.linear_num_value_heads // cfg.linear_num_key_heads
    got = jax.jit(lambda x, seg: gated_delta.gated_delta_rule(
        x["q"][None], x["k"][None], x["v"][None], x["g"][None],
        x["beta"][None], seg[None], cfg.gdn_chunk)[0])(x, seg)

    def exact(x, seg):
        with jax.default_matmul_precision("highest"):
            return ref.delta_rule(jnp.repeat(x["q"], r, axis=1),
                                  jnp.repeat(x["k"], r, axis=1), x["v"],
                                  x["g"], x["beta"], seg)

    want = np.asarray(jax.jit(exact)(x, seg), np.float64)
    diff = np.sqrt(np.square(np.asarray(got) - want).sum((-2, -1)))
    scale = np.sqrt(np.mean(np.square(want).sum((-2, -1))))
    finite = bool(np.isfinite(np.asarray(got)).all())
    worst = float(diff.max() / scale)
    say(f"reference: the scan on slow decays (g >= -{tol['scan_g_max']} a "
        f"row): median over rows of |diff| / rms |output| "
        f"{float(np.median(diff) / scale):.3e}, worst row {worst:.3e}")
    verdict.check(finite and worst <= tol["scan_row_worst_max"],
                  f"the scan on slow decays: worst row's |diff| / rms "
                  f"|output| {worst:.2e} <= {tol['scan_row_worst_max']}")
    return worst


def check_lower(verdict, config: dict, backbone, cfg, storage,
                program: float) -> float:
    """``program`` (the loaded logits' median over tokens, as
    ``check_reference`` read it) over the same median of the reference
    computed in bfloat16 THROUGHOUT, on the same model loaded back and
    the same sequences."""
    import jax.numpy as jnp

    from predictionio_tpu.core.workflow import prepare_deploy

    tol = config["reference"]
    model = prepare_deploy(engine_factory=config["engine_factory"],
                           variant_id="default", storage=storage).models[0]
    low, = shared.reference_logits(
        shared.Reference(backbone, cfg, jnp.bfloat16), model.device_params(),
        _last["batches"], int(tol["sequences_compared"]))
    lower = _compare(low, _last.pop("want"))["token_median"]
    share = program / max(lower, 1e-30)
    say(f"reference: the same median of the reference in bfloat16 "
        f"throughout {lower:.3e}; the program reads {share:.3f} of it")
    verdict.check(share <= tol["logits_vs_lower_max"],
                  f"the loss head's logits: the program's median over the "
                  f"all-bfloat16 reference's {share:.3f} <= "
                  f"{tol['logits_vs_lower_max']}")
    return share


def check_reference(verdict, config: dict, backbone, cfg, seed: int,
                    storage, fit: dict) -> dict:
    tol = config["reference"]
    _last["early"] = tol["early_positions"]
    out = _check(verdict, config, backbone, cfg, seed, storage, fit)
    out["vs_lower"] = check_lower(verdict, config, backbone, cfg, storage,
                                  out["loss"]["token_median"])
    early = out["loss"]["early_median"]
    verdict.check(early <= tol["logits_early_median_max"],
                  f"the logits of the first {tol['early_positions']} rows "
                  f"of each segment: median over tokens of |diff| / rms "
                  f"|logits| {early:.2e} <= "
                  f"{tol['logits_early_median_max']}")
    out["scan_row_worst"] = check_scan(verdict, tol, cfg, seed,
                                        _last["batches"].seg[0])
    return out


shared.shared.first_batches = first_batches
shared.shared.compare_logits = compare_logits
shared.check_reference = check_reference
run = shared.run
