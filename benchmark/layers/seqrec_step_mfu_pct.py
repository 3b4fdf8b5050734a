"""Layer "seqrec step": the model's operations of one train
(``roofline_seq.needs``: the configuration's shapes, the experts held
and the counter ``moe_pairs_here``; forward and backward, recomputation
not counted) over the device's busy seconds times the bf16 peak, in
percent: the share of the whole step."""


def read(obs):
    trace, need = obs.get("trace"), obs.get("need")
    if trace is None or not trace.busy_s or need is None:
        return None
    return 100.0 * need["train_flops"] / (
        trace.busy_s * obs["peaks"]["bf16_flops_per_s"])
