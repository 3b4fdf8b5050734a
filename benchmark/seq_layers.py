"""What the sequence cells' device-trace readers share: the scopes a
metric sums (``scope_reduce.scope_seconds`` of the traced train) and a
kernel's share of its roofline (``share_pct``, which the cells' own
``roofline_*.roofline_pct`` call too).

The experts are the one metric a scope alone does not hold: the compiler
writes the ``ragged-dot`` kernels' path as ``ragged-dot-none``, under no
scope, so ``seqrec.moe.experts`` holds only what runs BETWEEN the
grouped products. ``seconds(obs, "moe_experts")`` is the scope PLUS the
kernels by their operations' own names
(``scope_layers.ragged_dot_seconds``, what ``moe_ragged_dot_ms`` reads):
the experts' whole cost, for the time and for the share alike (PR 49;
before it the share divided the kernels' need by seconds that left the
kernels out)."""

from __future__ import annotations

import roofline
import scope_layers

#: metric → the scopes (innermost wins) whose device seconds it sums
SCOPES = {
    "mla_attention": ("seqrec.mla.attention",),
    "mla_proj": ("seqrec.mla",),
    "moe_route_dispatch": ("seqrec.moe.route", "seqrec.moe.dispatch",
                           "seqrec.moe.combine"),
    "moe_experts": ("seqrec.moe.experts",),
    "seqrec_ffn": ("seqrec.ffn",),
    "seqrec_head_loss": ("seqrec.head",),
    "seqrec_optimizer": ("seqrec.optimizer",),
}


def seconds(obs, metric: str):
    """Device seconds of ONE traced train under the metric's scopes —
    for ``moe_experts`` with the ``ragged-dot`` kernels, which lie under
    no scope; None where the trace names none of the scopes."""
    secs = scope_layers.seconds(obs, *SCOPES[metric])
    if secs is not None and metric == "moe_experts":
        secs += scope_layers.ragged_dot_seconds(obs)
    return secs


def share_pct(obs, secs, part: str):
    """The least time the chip could take for what ``part`` of
    ``obs["need"]`` needs over ``secs`` of device time, in percent; None
    (never 0) where either is missing."""
    need = obs.get("need")
    if not secs or need is None or part not in need or "peaks" not in obs:
        return None
    least, _bound = roofline.least_seconds(need[part], obs["peaks"])
    return 100.0 * least / secs


def roofline_pct(obs, metric: str, part: str):
    return share_pct(obs, seconds(obs, metric), part)
