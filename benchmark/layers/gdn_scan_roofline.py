"""Layer "kernels": the gated delta rule's share of its roofline, in
percent: the least time the chip could take for the RECURRENCE of one
train (``roofline_qwen3next.needs``: 7 · 128 · 128 operations a real
row, value head and linear layer forward, twice that backward; q, k, v,
g, β and o moved once forward and with their cotangents backward;
recomputation and the chunked form's own products not counted) over
``gdn_scan_ms``'s time."""

import roofline_qwen3next


def read(obs):
    return roofline_qwen3next.roofline_pct(obs, "gdn_scan", "gdn_scan")
