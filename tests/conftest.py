"""Test harness configuration.

All tests run JAX on CPU with a *virtual 8-device mesh* — the analogue of
the reference's `SparkContext("local[*]")` trick (SURVEY.md §4): every
collective / sharding / pjit code path is exercised with real SPMD
semantics, no TPU required.

``JAX_PLATFORMS=cpu`` (set here unless the caller chose otherwise) keeps
every test on the CPU backend; ``XLA_FLAGS`` gives it 8 virtual devices
and ``PIO_MESH_PLATFORM=cpu`` points the framework's mesh construction
at them. Both must be set before jax is imported.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["PIO_MESH_PLATFORM"] = "cpu"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from predictionio_tpu.storage.meta import MetaStore  # noqa: E402
from predictionio_tpu.storage.models import MemoryModelStore  # noqa: E402
from predictionio_tpu.data.events import MemoryEventStore  # noqa: E402
from predictionio_tpu.storage.registry import Storage, StorageConfig, set_storage  # noqa: E402


@pytest.fixture()
def storage(tmp_path):
    """A fresh, fully in-memory Storage installed as process default.
    Its home (train checkpoints, scan cache, model registry) is the
    test's own directory: under xdist two workers training the same
    engine id in one shared ``~/.pio_store/train_ckpt`` read each
    other's half-written checkpoint steps."""
    st = Storage(StorageConfig(metadata_type="MEMORY",
                               eventdata_type="MEMORY",
                               modeldata_type="MEMORY",
                               home=str(tmp_path / "pio_home")))
    # force instantiation so the fixtures are shared instances
    st._meta = MetaStore(":memory:")
    st._events = MemoryEventStore()
    st._models = MemoryModelStore()
    set_storage(st)
    yield st
    set_storage(None)


@pytest.fixture(scope="session")
def cpu_mesh():
    """8-virtual-device CPU mesh for collective/sharding tests."""
    from predictionio_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(axes={"data": 8}))


def pytest_sessionfinish(session, exitstatus):
    """When ``PIO_TEST_INCIDENT_EXPORT`` names a directory, copy every
    incident bundle the run left under the pytest basetemp into it —
    CI uploads these as postmortem artifacts on failure. A bundle is
    any directory holding a ``manifest.json`` (chaos-marked tests
    write real ones via the flight recorder)."""
    export = os.environ.get("PIO_TEST_INCIDENT_EXPORT")
    if not export:
        return
    import shutil

    tmp = session.config._tmp_path_factory.getbasetemp() \
        if hasattr(session.config, "_tmp_path_factory") else None
    if tmp is None or not tmp.exists():
        return
    os.makedirs(export, exist_ok=True)
    for manifest in tmp.rglob("manifest.json"):
        bundle = manifest.parent
        dest = os.path.join(export, bundle.name)
        try:
            shutil.copytree(str(bundle), dest, dirs_exist_ok=True)
        except OSError:
            pass  # artifact export is best-effort, never a test failure
