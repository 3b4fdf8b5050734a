"""Layer "kernels": ``gather_gram``'s share of its roofline, in percent:
the least time the chip could take for what the kernel's calls need
(``roofline.gather_gram_need`` on the prepared layout, against the peaks
of ``peaks.json``; bound by bytes) over the kernel's time in the trace."""

import roofline


def read(obs):
    trace, need = obs.get("trace"), obs.get("gather_gram_need")
    secs = trace.seconds_of("gather_gram") if trace is not None else None
    if not secs or need is None or "peaks" not in obs:
        return None
    least, _bound = roofline.least_seconds(need, obs["peaks"])
    return 100.0 * least / secs
