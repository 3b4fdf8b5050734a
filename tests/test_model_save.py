"""The save writes a model's arrays ONCE: ``Algorithm.save_model`` may
return parts (a pickled head, then the arrays' own buffers),
``run_train`` frames them without joining, ``LocalFSModelStore``
streams them into ``model.bin`` under one running SHA-256 — and
``prepare_deploy`` hands each ``load_model`` a view of its stretch.
What a ``model.bin`` from before the framing held still loads."""

import contextlib
import datetime as dt
import glob
import hashlib
import io
import json
import os
import pickle
import shutil
import struct
import tracemalloc

import numpy as np
import pytest

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
)
from predictionio_tpu.controller.engine import EngineFactory
from predictionio_tpu.core.workflow import (
    frame_models,
    prepare_deploy,
    run_train,
)
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.events import MemoryEventStore
from predictionio_tpu.storage.meta import MetaStore
from predictionio_tpu.storage.models import (
    LocalFSModelStore,
    MemoryModelStore,
)
from predictionio_tpu.storage.registry import Storage, StorageConfig
from predictionio_tpu.utils import integrity, model_parts, tracing
from predictionio_tpu.utils.bimap import BiMap
from test_templates import seed_views
from test_workflow import seed_ratings

TEMPLATES = "predictionio_tpu.templates."
REC = TEMPLATES + "recommendation.engine:engine_factory"
SEQ = TEMPLATES + "sequentialrec.engine:engine_factory"
SIM = TEMPLATES + "similarproduct.engine:engine_factory"
ECOMM = TEMPLATES + "ecommercerecommendation.engine:engine_factory"

#: a sample ``glm4_moe_lite`` architecture, small enough for the CPU
SEQ_ARCH = dict(
    model_type="glm4_moe_lite", hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=2, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, ep_size=1, num_experts_per_tok=2,
    num_hidden_layers=3, vocab_size=16, seq_len=64, seqs_per_step=2,
    attn_block=32, token_chunk=64, init_std=0.02, matmul_dtype="float32")


def _variant(factory, app, name, **params):
    return {"id": "default", "engineFactory": factory,
            "datasource": {"params": {"appName": app}},
            "algorithms": [{"name": name, "params": params}]}


def _seed_histories(storage, app_name):
    app = storage.meta.create_app(app_name, "")
    storage.events.init_channel(app.id)
    t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
    storage.events.insert_batch([
        Event(event="view", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{(u + t) % 8}",
              event_time=t0 + dt.timedelta(minutes=t))
        for u in range(20) for t in range(14)], app.id)


#: template -> (factory, variant, how to put its events in the store)
CASES = {
    "recommendation": (
        REC, _variant(REC, "TestApp", "als", rank=8, numIterations=2),
        lambda st: seed_ratings(st)),
    "sequentialrec": (
        SEQ, _variant(SEQ, "SeqApp", "seqrec", epochs=1, lr=0.003, seed=5,
                      architecture=SEQ_ARCH),
        lambda st: _seed_histories(st, "SeqApp")),
    "similarproduct": (
        SIM, _variant(SIM, "SPApp", "als", rank=8, numIterations=2),
        lambda st: seed_views(st, "SPApp")),
    "ecommercerecommendation": (
        ECOMM, _variant(ECOMM, "ECApp", "ecomm", rank=8, numIterations=2),
        lambda st: seed_views(st, "ECApp", with_buys=True)),
}


def _storage(home, models=None):
    """Meta and events in memory; the models on the local file system
    under ``home`` (the default backend) or in ``models``."""
    st = Storage(StorageConfig(
        metadata_type="MEMORY", eventdata_type="MEMORY",
        modeldata_type="LOCALFS" if models is None else "MEMORY",
        home=str(home)))
    st._meta = MetaStore(":memory:")
    st._events = MemoryEventStore()
    st._models = models
    return st


def _arrays(model):
    """The arrays of a model of any of the four templates, by name."""
    if isinstance(getattr(model, "params", None), dict):     # a tree
        import jax

        return {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_leaves_with_path(model.params)}
    names = ("U", "V", "popularity")
    return {n: getattr(model, n) for n in names if hasattr(model, n)}


def _same_bits(got, want):
    assert got.keys() == want.keys() and got
    for name, a in want.items():
        b, a = np.asarray(got[name]), np.asarray(a)
        assert (b.dtype, b.shape) == (a.dtype, a.shape), name
        assert b.tobytes() == a.tobytes(), name


@contextlib.contextmanager
def _keeping_trained(kept):
    """``Engine.train`` also leaves the models it returns in ``kept``."""
    train = Engine.train

    def keeping(self, ctx, engine_params):
        kept[:] = train(self, ctx, engine_params)
        return kept

    Engine.train = keeping
    try:
        yield
    finally:
        Engine.train = train


@pytest.fixture(scope="module")
def trained(request, tmp_path_factory):
    """ONE train of the template on the local file system: the store,
    the instance, the model as the algorithm returned it, the verb's
    spans."""
    factory, variant, seed = CASES[request.param]
    st = _storage(tmp_path_factory.mktemp(request.param))
    seed(st)
    models = []
    with _keeping_trained(models):
        iid = run_train(factory, variant=variant, storage=st, use_mesh=False)
    engine = EngineFactory.create(factory)
    (_, algo), = engine.make_algorithms(engine.params_from_variant(variant))
    return {"template": request.param, "storage": st, "iid": iid,
            "model": models[0], "algo": algo,
            "tree": tracing.last_verb("train.run"),
            "dir": st.models.model_dir(iid)}


def _trained_on(*templates):
    return pytest.mark.parametrize("trained", templates, indirect=True)


every_template = _trained_on(*sorted(CASES))
#: the two templates the benchmark's cells run
cells = _trained_on("recommendation", "sequentialrec")


def _deploy_blob(trained, blob):
    """``prepare_deploy`` of the trained instance from a store that
    holds ``blob`` for it."""
    st = _storage(trained["storage"].config.home, MemoryModelStore())
    st._meta, st._events = trained["storage"].meta, trained["storage"].events
    st.models.put(trained["iid"], blob)
    return prepare_deploy(instance_id=trained["iid"], storage=st).models


# -- (a) the round trip ------------------------------------------------------


@every_template
def test_round_trip_on_localfs_is_bit_for_bit(trained):
    deployed = prepare_deploy(instance_id=trained["iid"],
                              storage=trained["storage"])
    _same_bits(_arrays(deployed.models[0]), _arrays(trained["model"]))


@every_template
def test_loaded_arrays_are_views_of_the_blob_and_aligned(trained):
    model = prepare_deploy(instance_id=trained["iid"],
                           storage=trained["storage"]).models[0]
    for name, a in _arrays(model).items():
        a = np.asarray(a)
        assert not a.flags.owndata and not a.flags.writeable, name
        assert a.flags.aligned, name


# -- (b) the digest ----------------------------------------------------------


@cells
def test_sidecar_is_sha256_of_the_files_bytes(trained):
    with open(os.path.join(trained["dir"], "model.bin"), "rb") as f:
        blob = f.read()
    with open(os.path.join(trained["dir"], "model.bin.sha256")) as f:
        assert f.read() == hashlib.sha256(blob).hexdigest()
    assert trained["storage"].models.get(trained["iid"]) == blob


@cells
def test_a_flipped_byte_is_refused_by_get(trained, tmp_path):
    shutil.copytree(trained["dir"], tmp_path / "copy")
    path = tmp_path / "copy" / "model.bin"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 1
    path.write_bytes(blob)
    with pytest.raises(integrity.IntegrityError):
        LocalFSModelStore(str(tmp_path)).get("copy")


# -- (c) every backend receives the same bytes --------------------------------


@cells
def test_default_put_parts_hands_memory_the_file_localfs_wrote(
        trained, tmp_path):
    parts = frame_models(
        [trained["algo"].save_model(trained["model"], None)])
    memory, local = MemoryModelStore(), LocalFSModelStore(str(tmp_path))
    assert memory.put_parts("m", parts) is False
    assert local.put_parts("m", parts) is True
    written = (tmp_path / "m" / "model.bin").read_bytes()
    assert memory.get("m") == written == b"".join(parts)
    # and the train's own file is that blob too
    assert trained["storage"].models.get(trained["iid"]) == written


def test_put_of_bytes_is_put_parts_of_one_part(tmp_path):
    store = LocalFSModelStore(str(tmp_path))
    store.put("a", b"one blob")
    assert store.put_parts("b", [b"one ", memoryview(b"blob")]) is True
    assert store.get("a") == store.get("b") == b"one blob"
    assert ((tmp_path / "a" / "model.bin.sha256").read_text()
            == (tmp_path / "b" / "model.bin.sha256").read_text()
            == hashlib.sha256(b"one blob").hexdigest())


# -- (d) what a model.bin held before the framing still loads -----------------


def _npz_blob(model, **head):
    """``save_model`` of the ALS templates before PR 34."""
    buf = io.BytesIO()
    np.savez_compressed(buf, **{
        "pop" if n == "popularity" else n: a
        for n, a in _arrays(model).items()})
    return pickle.dumps(dict(head, npz=buf.getvalue()))


@_trained_on("recommendation", "similarproduct", "ecommercerecommendation")
def test_old_pickled_list_with_an_npz_dictionary_loads(trained):
    model = trained["model"]
    head = {"item_ids": model.item_ids.to_dict()}
    if trained["template"] == "similarproduct":
        head.update(cats=model.item_categories, ann_shortlist=128,
                    ann_shards=0)
    else:
        head.update(user_ids=model.user_ids.to_dict())
    if trained["template"] == "ecommercerecommendation":
        head.update(cats=model.item_categories, app_name=model.app_name,
                    params=None)
    (loaded,) = _deploy_blob(
        trained, pickle.dumps([_npz_blob(model, **head)]))
    _same_bits(_arrays(loaded), _arrays(model))
    assert loaded.item_ids.to_dict() == model.item_ids.to_dict()


@_trained_on("sequentialrec")
def test_old_pickled_list_with_a_raw_magic_blob_loads(trained):
    import jax

    from predictionio_tpu.templates.sequentialrec import engine as eng

    model = trained["model"]
    leaves, treedef = jax.tree.flatten(model.params)
    head = pickle.dumps({
        "tree": jax.tree.unflatten(treedef, range(len(leaves))),
        "leaves": [(a.dtype.str, a.shape) for a in leaves],
        "item_ids": model.item_ids.to_dict(), "app_name": model.app_name,
        "hp": model.hp, "model_type": model.model_type,
        "algo_params": model.algo_params, "losses": model.losses})
    blob = b"".join([eng._RAW_MAGIC, struct.pack("<Q", len(head)), head]
                    + [np.ascontiguousarray(a).tobytes() for a in leaves])
    (loaded,) = _deploy_blob(trained, pickle.dumps([blob]))
    _same_bits(_arrays(loaded), _arrays(model))
    assert loaded.model_type == model.model_type


# -- (e) an engine of a user: bytes, a default pickle, nothing ----------------


class _Words(DataSource):
    def read_training(self, ctx):
        return ["a", "b", "a"]


class _Pickled(Algorithm):
    """The default persistence: ``pickle.dumps(model)``."""

    def train(self, ctx, data):
        return {"counts": np.arange(6, dtype=np.int64), "words": data}

    def predict(self, model, query):
        return model["words"]


class _Nothing(Algorithm):
    """Persists nothing: ``load_model`` rebuilds the model."""

    def train(self, ctx, data):
        return len(data)

    def predict(self, model, query):
        return model

    def save_model(self, model, instance_dir):
        return None

    def load_model(self, blob, instance_dir):
        assert blob is None
        return "rebuilt"


class _Json(Algorithm):
    """``bytes`` out, and a ``load_model`` that needs ``bytes`` back:
    ``json.loads`` takes no memoryview, a view has no ``decode``."""

    def train(self, ctx, data):
        return {"words": data}

    def predict(self, model, query):
        return model

    def save_model(self, model, instance_dir):
        return json.dumps(model).encode()

    def load_model(self, blob, instance_dir):
        assert isinstance(blob, bytes)
        return json.loads(blob.decode())


def engine_factory():
    return Engine(data_source_cls=_Words, preparator_cls=IdentityPreparator,
                  algorithm_cls_map={"pickled": _Pickled, "nothing": _Nothing,
                                     "json": _Json},
                  serving_cls=FirstServing)


USER = __name__ + ":engine_factory"
USER_VARIANT = {"id": "default", "engineFactory": USER, "algorithms": [
    {"name": "pickled", "params": {}}, {"name": "nothing", "params": {}},
    {"name": "json", "params": {}}]}


def _check_user_models(models):
    pickled, nothing, from_json = models
    assert pickled["words"] == ["a", "b", "a"]
    assert pickled["counts"].tolist() == [0, 1, 2, 3, 4, 5]
    assert nothing == "rebuilt"
    assert from_json == {"words": ["a", "b", "a"]}


@pytest.mark.parametrize("backend", ["localfs", "memory"])
def test_engine_with_bytes_a_default_pickle_and_none(tmp_path, backend):
    st = _storage(tmp_path,
                  MemoryModelStore() if backend == "memory" else None)
    iid = run_train(USER, variant=USER_VARIANT, storage=st, use_mesh=False)
    _check_user_models(prepare_deploy(instance_id=iid, storage=st).models)
    put = [s for s in tracing.last_verb("train.run")
           if s["name"] == "model.put"][0]["attrs"]
    # the frame's header and two stretches, padding between them
    assert put["parts"] == 4
    assert put["streamed"] == (1 if backend == "localfs" else 0)
    assert put["bytes"] == len(st.models.get(iid))


def test_old_pickled_list_with_a_default_pickle_and_none_loads(tmp_path):
    st = _storage(tmp_path, MemoryModelStore())
    iid = run_train(USER, variant=USER_VARIANT, storage=st, use_mesh=False)
    st.models.put(iid, pickle.dumps([
        pickle.dumps({"counts": np.arange(6, dtype=np.int64),
                      "words": ["a", "b", "a"]}),
        None,
        json.dumps({"words": ["a", "b", "a"]}).encode()]))
    _check_user_models(prepare_deploy(instance_id=iid, storage=st).models)


def test_a_blob_cut_short_is_refused(tmp_path):
    st = _storage(tmp_path, MemoryModelStore())
    iid = run_train(USER, variant=USER_VARIANT, storage=st, use_mesh=False)
    st.models.put(iid, st.models.get(iid)[:-1])
    with pytest.raises(ValueError, match="cut short"):
        prepare_deploy(instance_id=iid, storage=st)


# -- (f) no whole-model copy --------------------------------------------------


def _big_model(template):
    """A model of the template with 72 MiB of arrays, made up."""
    rng = np.random.default_rng(0)
    big = rng.standard_normal((65536, 256), dtype=np.float32)
    small = rng.standard_normal((8192, 256), dtype=np.float32)
    if template == "recommendation":
        from predictionio_tpu.templates.recommendation.engine import ALSModel

        return ALSModel(big, small,
                        BiMap({f"u{i}": i for i in range(len(big))}),
                        BiMap({f"i{i}": i for i in range(len(small))}))
    from predictionio_tpu.models.seq_rec import SeqRecParams
    from predictionio_tpu.templates.sequentialrec.engine import SeqRecModel

    # the head column-major, as a fetch from the TPU hands it back
    return SeqRecModel({"params": {"embed": small, "head": big.T}},
                       BiMap({f"i{i}": i for i in range(16)}), "SeqApp",
                       SeqRecParams(), None, np.zeros(1, np.float32))


@pytest.mark.parametrize("template", ["recommendation", "sequentialrec"])
def test_the_save_copies_no_array(tmp_path, monkeypatch, template):
    """Under ``tracemalloc``, what the verb allocates from the moment
    the algorithms return stays under a quarter of the arrays' bytes
    (the join and the pickle of the parent held them twice over)."""
    factory, variant, _ = CASES[template]
    model = _big_model(template)
    nbytes = sum(np.asarray(a).nbytes for a in _arrays(model).values())
    assert nbytes >= 64 << 20
    st = _storage(tmp_path)

    def made_up(self, ctx, engine_params):
        tracemalloc.start()
        return [model]

    monkeypatch.setattr(Engine, "train", made_up)
    try:
        iid = run_train(factory, variant=variant, storage=st, use_mesh=False)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < nbytes / 4, f"{peak} bytes allocated to save {nbytes}"
    spans = {s["name"]: s.get("attrs")
             for s in tracing.last_verb("train.run")}
    assert spans["model.put"]["streamed"] == 1
    assert spans["model.serialize"]["bytes"] >= nbytes
    assert os.path.getsize(os.path.join(st.models.model_dir(iid),
                                        "model.bin")) >= nbytes


# -- (g) a writer that fails ---------------------------------------------------


def _failing(parts):
    yield parts[0]
    raise OSError("the disk is full")


@pytest.mark.parametrize("earlier", [False, True])
def test_a_failed_write_leaves_no_file_and_an_earlier_model_untouched(
        tmp_path, earlier):
    store = LocalFSModelStore(str(tmp_path))
    if earlier:
        store.put("m", b"the earlier model")
    with pytest.raises(OSError, match="disk is full"):
        store.put_parts("m", _failing([b"first part", b"second part"]))
    assert glob.glob(str(tmp_path / "m" / ".atomic-*")) == []
    if earlier:
        assert store.get("m") == b"the earlier model"
        assert sorted(os.listdir(tmp_path / "m")) == ["model.bin",
                                                      "model.bin.sha256"]
    else:
        assert store.get("m") is None
        assert os.listdir(tmp_path / "m") == []


# -- (h) the counters that say the mechanism engaged --------------------------


@cells
def test_model_put_span_carries_parts_streamed_bytes(trained):
    spans = {s["name"]: s for s in trained["tree"]}
    put, serialize = spans["model.put"], spans["model.serialize"]
    assert (put["parentId"] == serialize["parentId"]
            == spans["train.save"]["spanId"])
    size = os.path.getsize(os.path.join(trained["dir"], "model.bin"))
    assert put["attrs"]["bytes"] == size
    assert put["attrs"]["streamed"] == 1
    # the frame's header, the algorithm's head, one part for each array
    assert put["attrs"]["parts"] == 2 + len(_arrays(trained["model"]))
    assert 0 < serialize["attrs"]["bytes"] < size


# -- (i) model.put's inside: write, sync, digest -------------------------------

PUT_CHILDREN = ["model.put.write", "model.put.sync", "model.put.digest"]


def _children_of_put(tree):
    (put,) = [s for s in tree if s["name"] == "model.put"]
    return put, [s for s in tree if s["parentId"] == put["spanId"]]


@cells
def test_model_put_has_three_children_that_cover_it(trained):
    put, kids = _children_of_put(trained["tree"])
    assert [k["name"] for k in kids] == PUT_CHILDREN
    for a, b in zip(kids, kids[1:]):
        assert put["startNs"] <= a["startNs"] <= a["endNs"] \
            <= b["startNs"] <= b["endNs"] <= put["endNs"]
    # host_untraced_s subtracts LEAF spans: what the three leave of
    # their parent is host time that no span names
    covered = sum(k["endNs"] - k["startNs"] for k in kids)
    assert 0 <= put["endNs"] - put["startNs"] - covered < 10e6
    size = os.path.getsize(os.path.join(trained["dir"], "model.bin"))
    assert kids[0]["attrs"] == {"bytes": size,
                                "parts": put["attrs"]["parts"]}


@pytest.mark.parametrize("in_verb", [True, False])
def test_the_blob_is_synced_under_its_name_before_the_digest_is_written(
        tmp_path, monkeypatch, in_verb):
    """The durable order, by the calls the store makes: the written
    file's fsync, then its name, then the directory's fsync — all
    three inside ``model.put.sync`` — and only then the same three for
    the sidecar, inside ``model.put.digest``. Outside a verb, with
    tracing off, the same calls and no span."""
    calls = []

    def noting(what, real):
        def call(*args):
            target = args[-1] if what == "replace" else os.readlink(
                f"/proc/self/fd/{args[0]}")
            calls.append((what, os.path.basename(target),
                          tracing.time.perf_counter_ns()))
            return real(*args)
        return call

    monkeypatch.setattr(os, "fsync", noting("fsync", os.fsync))
    monkeypatch.setattr(os, "replace", noting("replace", os.replace))
    store = LocalFSModelStore(str(tmp_path))
    parts = [b"head", memoryview(np.arange(6, dtype=np.float32)),
             np.arange(5, dtype=np.int64)]
    assert not tracing.TRACER.enabled
    before = tracing.last_verb("put.test")
    if in_verb:
        with tracing.verb("put.test"), tracing.span("model.put"):
            assert store.put_parts("m", parts) is True
    else:
        assert store.put_parts("m", parts) is True
    kinds = [(what, "tmp" if name.startswith(".atomic-") else name)
             for what, name, _ in calls]
    assert kinds == [("fsync", "tmp"), ("replace", "model.bin"),
                     ("fsync", "m"), ("fsync", "tmp"),
                     ("replace", "model.bin.sha256"), ("fsync", "m")]
    blob = b"".join(bytes(p) for p in parts)
    assert store.get("m") == blob
    with open(tmp_path / "m" / "model.bin.sha256") as f:
        assert f.read() == hashlib.sha256(blob).hexdigest()
    if not in_verb:
        assert tracing.last_verb("put.test") == before
        assert len(tracing.TRACER.ring) == 0
        return
    put, (write, sync, digest) = _children_of_put(
        tracing.last_verb("put.test"))
    assert [s["name"] for s in (write, sync, digest)] == PUT_CHILDREN
    assert write["attrs"] == {"bytes": len(blob), "parts": 3}
    for span, stamps in ((sync, calls[:3]), (digest, calls[3:])):
        assert all(span["startNs"] <= t <= span["endNs"]
                   for _, _, t in stamps)


def test_a_store_that_joins_opens_no_child_span():
    """Only the local file system has a write, a sync and a digest to
    tell apart; the default ``put_parts`` joins and ``put``s."""
    with tracing.verb("put.test"), tracing.span("model.put"):
        assert MemoryModelStore().put_parts("m", [b"a", b"b"]) is False
    assert [s["name"] for s in tracing.last_verb("put.test")] == [
        "put.test", "model.put"]


# -- the helper's own layout ---------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8", "float64"])
def test_pack_unpack_arrays_of_any_dtype_and_shape(dtype):
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    rng = np.random.default_rng(1)
    arrays = [rng.standard_normal(s).astype(dtype)
              for s in ((3, 5), (7,), (), (0, 4), (2, 3, 4))]
    # lying in memory with their axes in another order: written as
    # they lie; a slice with gaps: copied
    arrays += [np.asfortranarray(arrays[0]), arrays[4].transpose(0, 2, 1),
               arrays[4].transpose(2, 0, 1), arrays[0][:, ::2]]
    parts = model_parts.pack_arrays({"note": "x"}, arrays)
    assert [np.shares_memory(a, np.frombuffer(p, np.uint8))
            for a, p in zip(arrays[5:], parts[6:])] == [True, True, True,
                                                         False]
    assert len(parts[0]) % model_parts.ALIGN == 0
    head, got = model_parts.unpack_arrays(memoryview(b"".join(parts)))
    assert head["note"] == "x"
    for a, b in zip(arrays, got):
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        assert a.tobytes() == b.tobytes()
        if a.size and not a.flags.c_contiguous and a is not arrays[-1]:
            assert b.strides == a.strides
    assert model_parts.unpack_arrays(pickle.dumps({"npz": b""})) is None
