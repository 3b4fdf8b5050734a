"""What ONE train of the ``xing4_0`` cell needs, from the configuration's
shapes and the program's own counters: ``roofline_seq``'s counts for
this block (latent attention's product, the held experts' grouped
products, the whole step; no MTP term where the configuration has no
module) and beside them the residual stream's MIXER, ``mhc_mix`` — the
same work whatever implements it, never the passes a compiler's fusions
make. A forward pass costs 2 operations a multiply-add, the backward
pass twice the forward; recomputation is NOT counted.

The mixer, per real token, layer and SUBLAYER (two a layer), forward,
n = ``hc_mult`` copies of d float32:

- the read u = Σᵢ H_pre[i]·X[i]: the stream read once, n·d·4 B;
- the write-back X[i] ← Σⱼ H_res[i, j]·X[j] + H_post[i]·y: the stream
  and y read once, n·d·4 + d·4 B, the stream written once, n·d·4 B;
- 2·(2n + n²)·d operations (n multiply-adds a row of u, n + 1 a row of
  each copy).

u's own d·4 B and the coefficients' (2n + n²)·4 B a token are left
out: the need is a floor. 186,368 B a token and sublayer at the
published sizes, 1.118 MB a token and layer forward and backward — bound
by bytes 170 times over. φ's product (2·n·d·(2n + n²) operations a token
and sublayer) runs under ``seqrec.mhc.coef``, whose seconds the mixer's
share does not hold: its operations go into the whole step's
``train_flops`` only.

Also which ``seqrec.*`` scopes the cell's own device metrics sum
(``SCOPES``; the others are ``seq_layers.SCOPES``).
"""

from __future__ import annotations

import roofline_seq
import scope_layers
import seq_layers

#: metric → the scopes (innermost wins) whose device seconds it sums:
#: the mixes and, with them, the stream's copies and its fold
SCOPES = {
    "mhc_mix": ("seqrec.mhc.mix", "seqrec.mhc"),
    "mhc_coef": ("seqrec.mhc.coef",),
}
#: bytes of an element of the stream (float32)
STREAM = 4


def seconds(obs, metric: str):
    return scope_layers.seconds(obs, *SCOPES[metric])


def roofline_pct(obs, metric: str, part: str):
    return seq_layers.share_pct(obs, seconds(obs, metric), part)


def mixer_per_token(c) -> dict:
    """Bytes and operations of ONE forward pass of one sublayer's mixer
    on one token, and φ's operations beside them."""
    n, d = c.hc_mult, c.hidden_size
    width = n * (2 + n)
    return {"bytes": STREAM * (n * d + (n * d + d) + n * d),
            "flops": 2 * width * d,
            "coef_flops": 2 * n * d * width}


def needs(c, fit: dict, pack: dict) -> dict:
    """``fit``: the ``seqrec.fit`` span's attributes of the train
    (``steps``; ``moe_pairs_here`` over all its steps and layers;
    ``mhc_sublayers`` a step); ``pack``: the ``seqrec.pack`` span's
    (``sequences``, ``real_tokens``, ``attn_pairs`` of one epoch)."""
    out = roofline_seq.needs(c, fit, pack)
    epochs = fit["steps"] * c.seqs_per_step / max(pack["sequences"], 1)
    passes = 3 * pack["real_tokens"] * epochs * fit["mhc_sublayers"]
    one = mixer_per_token(c)
    out["mhc_mix"] = {"flops": passes * one["flops"],
                      "bytes": passes * one["bytes"]}
    out["train_flops"] += passes * (one["flops"] + one["coef_flops"])
    return out
