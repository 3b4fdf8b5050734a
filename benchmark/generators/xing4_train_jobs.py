"""Traffic kind ``xing4_train_jobs``: ``lfm2_train_jobs`` (whole warm
``pio train`` verbs of the ``sequentialrec`` template back to back, for
ANY block-stack backbone of the template's table, with its ``correct``
and its comparison against ``reference/<model_type>_jnp.py``) for the
``xing4_0`` backbone, whose residual stream is ``hc_mult`` copies mixed
by a Sinkhorn-made matrix a sublayer: its needs entered in the loaded
module's ``ROOFLINES``, and on that LOADED module (a PR that adds a cell
may edit no benchmark file that is there) two things MORE:

- the heads. The backbone trains 0 or 1 MTP modules by its
  configuration, the table's ``heads`` names both it MAY train, and the
  generator reads the tuple: it is handed the backbone with the heads
  THIS configuration trains (``num_nextn_predict_layers`` 0: the next
  item's alone);
- a check of the verb: ``mhc_ds_err`` — the largest |row or column sum
  of H_res − 1| over the layers and steps of the last timed verb, the
  ``seqrec.fit`` span's counter — under the configuration's
  ``correct.mhc_ds_err_max``;
- a check of the MIXER itself (``check_mixer``), which the generator's
  own three limits cannot see: one sublayer's coefficients, read and
  write-back (``ops/hyper_connections.py``, as the program runs them)
  at the timed shapes on seeded operands — four copies that DIFFER, b
  spread by 1 (the mixer's logits around 0: far from the identity),
  α 0.3 — against the reference's
  equations in float32: rms |difference| ÷ rms |reference| of the
  written stream and of u, the larger (``reference.mixer_rel_rms_max``).
  Where the coefficients start (H_pre ≈ 1/4, H_post ≈ 1, H_res near the
  identity, copies that differ by a quarter of a sublayer's output) the
  fold Σᵢ X[i] and a nearly uniform blend u are blind, to first order,
  to WHICH doubly-stochastic matrix mixed the copies: H_res used as its
  transpose moves the loaded logits' median by a tenth of what bfloat16
  operands do (``README_xing4.md``), and reads 0.2 here.

``run``, the phases, every other check and the last line are that
generator's.
"""

from __future__ import annotations

# the model FIRST: a tree without the backbone fails here, in seconds,
# before any data is made or a store imported
from predictionio_tpu.models import seq_backbone

seq_backbone.backbone("xing4_0")

import numpy as np  # noqa: E402

from predictionio_tpu.ops import hyper_connections as hc  # noqa: E402

from harness import load_module  # noqa: E402
from reference import xing4_0_jnp as ref  # noqa: E402

shared = load_module("generators", "lfm2_train_jobs")
shared.ROOFLINES["xing4_0"] = "roofline_xing4"

_check = shared.check_reference


def backbone_of(config: dict):
    """The table's backbone with the heads this configuration trains."""
    backbone = seq_backbone.backbone(config.get("model_type"))
    modules = int(config.get("num_nextn_predict_layers", 1))
    return backbone._replace(heads=backbone.heads[:1 + modules])


def mixer_operands(cfg, seed: int) -> dict:
    """Seeded operands of one sublayer's mixer at the configuration's
    shapes, one sequence: the stream X [n, S, d] (a common part and
    half as much of each copy's own), y [S, d], φ ~ normal(0,
    init_std), b ~ normal(b₀, 1) — b₀ where the program starts H_pre
    and H_post, 0 for ALL of the mixer's logits: a mixer far from the
    identity and from its transpose —, α = 0.3 thrice (α·m is then of
    order 1: H differs token by token)."""
    rng = np.random.default_rng([int(seed), 50])
    n, S, d, width = cfg.hc_mult, cfg.seq_len, cfg.hidden_size, cfg.hc_width

    def normal(*shape):
        return rng.standard_normal(shape, np.float32)

    start = np.concatenate([np.full(n, -np.log(n - 1.0)),
                            np.zeros(n + n * n)])
    return {"X": normal(1, S, d) + 0.5 * normal(n, S, d), "y": normal(S, d),
            "phi": cfg.init_std * normal(n, d, width),
            "b": (start + normal(width)).astype(np.float32),
            "alpha": np.full(3, 0.3, np.float32)}


def mixer_program(cfg):
    """``(u, the written stream [S, n, d])`` of the operands, by the
    program's operator."""
    import jax
    import jax.numpy as jnp

    def run(x):
        pre, post, res = hc.coefficients(
            x["X"], x["phi"], x["b"], x["alpha"], norm_eps=cfg.rms_norm_eps,
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
            dtype=jnp.dtype(cfg.matmul_dtype))
        return (hc.read(x["X"], pre),
                hc.write(x["X"], res, post, x["y"]).transpose(1, 0, 2))

    return jax.jit(run)


def mixer_reference(cfg, dtype=None):
    """The same by the reference's equations under ``highest``
    (``dtype``: everything computed lower; ``ref.FAULT`` is read when
    the result is first asked for)."""
    import jax
    import jax.numpy as jnp

    rcfg = dict(cfg.__dict__)

    def run(x):
        x = jax.tree.map(lambda a: a.astype(dtype or jnp.float32), x)
        Xt = x["X"].transpose(1, 0, 2)
        with jax.default_matmul_precision("highest"):
            pre, _, _ = ref.coefficients(x, Xt, rcfg)
            return (jnp.einsum("si,sid->sd", pre, Xt),
                    ref.hyper(x, Xt, lambda u: x["y"], rcfg))

    return jax.jit(run)


def mixer_rel_rms(got, want) -> float:
    """The larger, over u and the written stream, of rms |difference|
    ÷ rms |reference|."""
    def one(g, w):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        return float(np.sqrt(np.mean(np.square(g - w))
                             / np.mean(np.square(w))))

    return max(one(g, w) for g, w in zip(got, want))


def check_mixer(verdict, tol: dict, cfg, seed: int) -> float:
    import jax.numpy as jnp

    x = {k: jnp.asarray(v) for k, v in mixer_operands(cfg, seed).items()}
    got = mixer_program(cfg)(x)
    rel = mixer_rel_rms(got, mixer_reference(cfg)(x))
    finite = all(bool(np.isfinite(np.asarray(g)).all()) for g in got)
    verdict.check(finite and rel <= tol["mixer_rel_rms_max"],
                  f"one sublayer's mixer on seeded operands: rms |diff| / "
                  f"rms |reference| of u and the written stream {rel:.2e} "
                  f"<= {tol['mixer_rel_rms_max']}")
    return rel


def check_reference(verdict, config: dict, backbone, cfg, seed: int,
                    storage, fit: dict) -> dict:
    limit = config["correct"]["mhc_ds_err_max"]
    err = fit.get("mhc_ds_err")
    verdict.check(err is not None and err <= limit,
                  f"H_res's rows and columns sum to 1: the largest "
                  f"|sum - 1| over the verb's layers and steps "
                  f"{float('nan') if err is None else err:.2e} <= {limit}")
    out = _check(verdict, config, backbone, cfg, seed, storage, fit)
    out["mhc_ds_err"] = err
    out["mixer_rel_rms"] = check_mixer(verdict, config["reference"], cfg,
                                       seed)
    return out


shared.backbone_of = backbone_of
shared.check_reference = check_reference
run = shared.run
