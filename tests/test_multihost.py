"""Multi-host (DCN) execution path: REAL 2-process jax.distributed runs
over localhost — the proof the rendezvous, cross-host collectives, and
the run_train wiring work (SURVEY.md §2d P5/C2; the reference's
driver/executor control plane over netty RPC).

Each test spawns two subprocesses on the CPU platform with 2 virtual
devices each (a 4-device global mesh split across processes) and the
PIO_* rendezvous env vars that `parallel/distributed.initialize` (and
through it `run_train`) consumes.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(script: str, proc_id: int, port: int, extra_env=None):
    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    env.update({
        "PYTHONPATH": REPO,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "JAX_PLATFORMS": "cpu",
        "PIO_MESH_PLATFORM": "cpu",
        "PIO_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "PIO_NUM_PROCESSES": "2",
        "PIO_PROCESS_ID": str(proc_id),
    })
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-c", script],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _run_pair(script: str, extra_env=None, timeout=240):
    port = _free_port()
    procs = [_spawn(script, i, port, extra_env) for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {i} failed:\n{out}"
    return outs


COLLECTIVES = textwrap.dedent("""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    from predictionio_tpu.parallel import distributed

    multi = distributed.initialize()   # from the PIO_* env vars
    assert multi, "expected multi-process"
    assert jax.process_count() == 2
    assert len(jax.local_devices()) == 2
    assert len(jax.devices()) == 4
    distributed.barrier("pio_test_start")

    # control-plane broadcast (coordinator value wins)
    me = distributed.process_index()
    val = distributed.broadcast_from_coordinator(
        np.asarray([41.0 if me == 0 else -1.0], np.float32))
    assert float(np.asarray(val)[0]) == 41.0, val
    sid = distributed.broadcast_string("inst-xyz" if me == 0 else "")
    assert sid == "inst-xyz", sid

    # a cross-process collective: psum over the 4-device global mesh
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    x = jax.make_array_from_callback(
        (8,), sharding,
        lambda idx: np.arange(8, dtype=np.float32)[idx])
    sm = jax.shard_map

    def f(x):
        return jax.lax.psum(x.sum(), "data")

    total = jax.jit(sm(f, mesh=mesh, in_specs=P("data"), out_specs=P()))(x)
    assert float(np.asarray(total)) == 28.0, total
    distributed.barrier("pio_test_done")
    print("COLLECTIVES_OK", me)
""")


TRAIN = textwrap.dedent("""
    import json
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    import os
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.storage.registry import Storage, StorageConfig, set_storage

    st = Storage(StorageConfig(metadata_type="SQLITE",
                               eventdata_type="SQLITE",
                               modeldata_type="LOCALFS",
                               home=os.environ["PIO_HOME"]))
    set_storage(st)
    FACTORY = "predictionio_tpu.templates.recommendation.engine:engine_factory"
    VARIANT = {
        "id": "default",
        "engineFactory": FACTORY,
        "datasource": {"params": {"appName": "MHApp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 4, "numIterations": 2,
                                   "lambda": 0.1}}],
    }
    iid = run_train(FACTORY, variant=VARIANT, storage=st, use_mesh=True)
    print("TRAIN_OK", jax.process_index(), iid)
""")


@pytest.mark.scenario
class TestTwoProcess:
    def test_rendezvous_barrier_broadcast_psum(self):
        outs = _run_pair(COLLECTIVES)
        assert all("COLLECTIVES_OK" in o for o in outs)

    def test_run_train_two_processes(self, tmp_path):
        # seed a shared sqlite event store both processes will read
        home = str(tmp_path / "pio_home")
        from predictionio_tpu.storage.registry import Storage, StorageConfig
        from tests.test_workflow import seed_ratings

        st = Storage(StorageConfig(metadata_type="SQLITE",
                                   eventdata_type="SQLITE",
                                   modeldata_type="LOCALFS", home=home))
        seed_ratings(st, app_name="MHApp")

        outs = _run_pair(TRAIN, extra_env={"PIO_HOME": home})
        ids = set()
        for o in outs:
            line = [l for l in o.splitlines() if l.startswith("TRAIN_OK")][-1]
            ids.add(line.split()[-1])
        assert len(ids) == 1, f"instance id differed across hosts: {ids}"

        # coordinator-only writes: exactly ONE engine instance row,
        # COMPLETED, and a loadable model
        st2 = Storage(StorageConfig(metadata_type="SQLITE",
                                    eventdata_type="SQLITE",
                                    modeldata_type="LOCALFS", home=home))
        instances = st2.meta.list_engine_instances()
        assert len(instances) == 1
        assert instances[0].status == "COMPLETED"
        from predictionio_tpu.core.workflow import prepare_deploy

        dep = prepare_deploy(
            engine_factory="predictionio_tpu.templates.recommendation."
                           "engine:engine_factory", storage=st2)
        res = dep.query({"user": "0", "num": 3})
        assert len(res["itemScores"]) == 3
