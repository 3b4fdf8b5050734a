"""Layer "ALS iteration": the whole ALS step's share of the chip's peak,
in percent: the operations the mathematics of ONE train needs
(``roofline.train_flops`` of the cell's interactions, users, items, rank
and iterations — per interaction a rank-one update of a k × k system on
each side, per entity one Cholesky solve; whatever kernel does them)
over the device's busy seconds (what ``als_device_s`` reads) times the
bf16 peak of ``peaks.json``.

A BOUND, not a grade: the products run in float32 ``HIGHEST`` (six
bf16 passes) and the step is bound by bytes, so the share is small. It
is here so that a PR which takes ``gather_gram`` off the path — and
leaves ``gather_gram_roofline`` silent — still has a share of the whole
step that bounds its claim."""


def read(obs):
    trace, flops = obs.get("trace"), obs.get("als_train_flops")
    if (trace is None or not trace.busy_s or flops is None
            or "peaks" not in obs):
        return None
    return 100.0 * flops / (trace.busy_s
                            * obs["peaks"]["bf16_flops_per_s"])
