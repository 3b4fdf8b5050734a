"""What the sequence cell's device-trace readers share: the scopes a
metric sums (``scope_reduce.scope_seconds`` of the traced train) and a
kernel's share of its roofline."""

from __future__ import annotations

import roofline

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
    scopes = obs.get("scopes")
    if not scopes:
        return None
    hit = [scopes[s] for s in SCOPES[metric] if s in scopes]
    return sum(hit) if hit else None


def roofline_pct(obs, metric: str, part: str):
    """The least time the chip could take for what ``part`` of
    ``obs["need"]`` needs over the metric's device time, in percent."""
    secs, need = seconds(obs, metric), obs.get("need")
    if not secs or need is None or "peaks" not in obs:
        return None
    least, _bound = roofline.least_seconds(need[part], obs["peaks"])
    return 100.0 * least / secs
