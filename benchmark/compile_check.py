#!/usr/bin/env python3
"""Rehearsal without the chip: compile a configuration's WHOLE train
program at full size for a described ``v5e:2x2`` device and print the
compiler's ``memory_analysis``.

    JAX_PLATFORMS=cpu python benchmark/compile_check.py <config> [gram_mode]

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached: what it refuses here (a kernel, a
program that does not fit 16 GB) it would refuse on the chip, at no chip
time. The program is built as ``als_train_prepared`` builds it on a TPU
(platform "tpu", the Gram mode ``auto`` resolves to there, blocks of
``checkpoint_every`` = 5 iterations). Nothing runs: a compile that
passes is not a chip run and gives no time.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))


def main(config_name: str, gram_mode: str = "pallas") -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from datagen import Interactions
    from predictionio_tpu.models import als
    from predictionio_tpu.utils.opcount import _host_side_bufs

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(BENCH, "configs", f"{config_name}.json")) as f:
        config = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    print(f"described device: {topo.devices[0].device_kind}", flush=True)
    t0 = time.perf_counter()
    data = Interactions(config, config["values"], config["heldout_share"], 1)
    print(f"data: {data.nnz:,} interactions in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    t0 = time.perf_counter()
    prep = als.als_prepare(als.RatingsCOO(
        data.users, data.items, data.values, data.n_users, data.n_items))
    print(f"als_prepare: {time.perf_counter() - t0:.1f} s (this host)")
    for name, side in (("user", prep.u_side), ("item", prep.i_side)):
        dense = side.geometry[1]
        print(f"{name} side: dense head {dense}; buckets (C, entities, "
              f"slab, n_slabs, seg): {side.geometry[2]}", flush=True)

    def sds(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    train = als._compiled_bucketed(
        prep.u_side.geometry, prep.i_side.geometry, prep.n_users,
        prep.n_items, config["rank"], 5, bool(config["implicit"]), True,
        "tpu", False, als._gram_precision(), gram_mode)
    args = jax.tree.map(sds, (
        _host_side_bufs(prep.u_side), _host_side_bufs(prep.i_side),
        np.zeros((prep.n_items, config["rank"]), np.float32),
        np.float32(config["lambda"]), np.float32(config["alpha"])))
    t0 = time.perf_counter()
    lowered = train.lower(*args)
    print(f"lowered in {time.perf_counter() - t0:.1f} s", flush=True)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    print(f"compiled in {time.perf_counter() - t0:.1f} s (this host's "
          f"CPU; not a chip time); gather_gram kernel in the program: "
          f"{'tpu_custom_call' in compiled.as_text()}")
    print(f"memory_analysis: arguments {mem.argument_size_in_bytes / 1e9:.2f}"
          f" GB, outputs {mem.output_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, together "
          f"{total / 1e9:.2f} GB of 16 GB")
    return 0 if total < 16e9 else 1


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
