"""Layer "layout": the benchmark's own clock around one direct
``als_prepare(coo)`` on the cell's data, outside the window (inside a
train the step hides in ``train:als``)."""


def read(obs):
    return obs.get("als_prepare_s")
