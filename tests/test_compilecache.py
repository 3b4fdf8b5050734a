"""Persistent XLA compile cache wiring (utils/compilecache).

The reference has no run-time compile step (Spark ships bytecode); here
every ``pio train``'s wall-clock depends on this module wiring JAX's
persistent cache correctly. The directory's path is part of the cache
key, so it is placed from outside (``JAX_COMPILATION_CACHE_DIR``) or is
one fixed path in the checkout — never under ``PIO_HOME``.
"""

import os
import subprocess
import sys
import textwrap
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.utils import compilecache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_enabled(monkeypatch):
    """Each test sees a fresh module (enable() is once-per-process)."""
    monkeypatch.setattr(compilecache, "_enabled", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_used_and_not_overwritten(tmp_path, monkeypatch):
    target = tmp_path / "from_env"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    writes = []
    real_update = jax.config.update
    with monkeypatch.context() as m:
        m.setattr(jax.config, "update",
                  lambda k, v: (writes.append(k), real_update(k, v))[1])
        assert compilecache.enable() == str(target)
    assert target.is_dir()
    # JAX reads the variable itself: the program writes no directory
    assert "jax_compilation_cache_dir" not in writes


def test_unset_is_the_fixed_in_checkout_dir(monkeypatch):
    got = compilecache.enable()
    assert got == compilecache.DEFAULT_DIR == os.path.join(REPO,
                                                           ".jax_cache")
    assert os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got
    # entries the ALS program sizes actually hit (default 60s/minsize
    # would skip everything but the biggest program)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_two_pio_homes_share_one_directory(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_HOME", str(tmp_path / "home_a"))
    first = compilecache.enable()
    monkeypatch.setattr(compilecache, "_enabled", False)
    monkeypatch.setenv("PIO_HOME", str(tmp_path / "home_b"))
    assert compilecache.enable() == first == compilecache.DEFAULT_DIR
    assert not (tmp_path / "home_a").exists()   # nothing under a home


def test_idempotent(tmp_path, monkeypatch):
    first = compilecache.enable()
    before = jax.config.jax_compilation_cache_dir
    # second call is a no-op returning the same dir (config untouched)
    with monkeypatch.context() as m:
        m.setattr(jax.config, "update",
                  lambda *a: pytest.fail("enable() wrote config twice"))
        assert compilecache.enable() == first
    assert jax.config.jax_compilation_cache_dir == before


CHILD = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    from jax import monitoring
    from predictionio_tpu.utils import compilecache

    seen = {"hits": 0, "requests": 0}
    monitoring.register_event_listener(lambda name, **kw: seen.update(
        hits=seen["hits"] + (name == "/jax/compilation_cache/cache_hits"),
        requests=seen["requests"] + (
            name == "/jax/compilation_cache/compile_requests_use_cache")))
    cache_dir = compilecache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    salt = float(sys.argv[1])
    jax.jit(lambda x: (x * salt).sum())(jnp.arange(8.0)).block_until_ready()
    print(json.dumps({"dir": cache_dir, **seen}))
""")


def _child(env_extra, salt):
    import json

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_extra)
    out = subprocess.run([sys.executable, "-c", CHILD, salt], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_second_process_with_another_home_compiles_nothing_new(tmp_path):
    """Unset: two runs with different PIO_HOMEs share the fixed dir and
    the second one's program comes out of the cache."""
    salt = str(uuid.uuid4().int % 10**9 + 0.5)   # a program nobody cached
    a = _child({"PIO_HOME": str(tmp_path / "a")}, salt)
    b = _child({"PIO_HOME": str(tmp_path / "b")}, salt)
    assert a["dir"] == b["dir"] == compilecache.DEFAULT_DIR
    assert a["hits"] < a["requests"]          # cold: something compiled
    assert b["hits"] == b["requests"] > 0     # warm: all from the cache


def test_env_dir_receives_the_cache_entries(tmp_path):
    target = tmp_path / "placed"
    doc = _child({"JAX_COMPILATION_CACHE_DIR": str(target)}, "3.5")
    assert doc["dir"] == str(target)
    assert any(target.iterdir())


def test_aot_warmup_smoke_with_persistent_cache(monkeypatch):
    """CPU AOT-warmup smoke (tier-1): the deploy-time bucket warmup
    (server/aot) runs with the persistent compile cache pointed at a
    real directory — explicit lower().compile() must coexist with the
    cache wiring — and a same-geometry re-warm is pure in-process
    executable-cache hits (the compile-free /reload contract)."""
    import numpy as np

    from predictionio_tpu.models.als import ResidentScorer
    from predictionio_tpu.server.aot import BucketLadder

    monkeypatch.setenv("PIO_ALS_SERVE", "device")
    compilecache.enable()

    rng = np.random.default_rng(0)
    U = rng.standard_normal((64, 8)).astype(np.float32)
    V = rng.standard_normal((2100, 8)).astype(np.float32)
    ladder = BucketLadder([1, 2])
    first = ResidentScorer(U, V).warm_buckets(ladder, ks=(5,))
    assert first["targets"] == 2
    again = ResidentScorer(U, V).warm_buckets(ladder, ks=(5,))
    assert again == {"targets": 2, "compiled": 0, "cached": 2}
    # the warmed shape serves without error under the enabled cache
    sc = ResidentScorer(U, V)
    sc.warm_buckets(ladder, ks=(5,))
    [(iv, vv)] = sc.recommend_batch(np.asarray([3], np.int32), 5)
    assert iv.shape == (5,) and vv.shape == (5,)


# -- compile as a layer of the verb record -------------------------------------


@pytest.fixture
def record():
    """The listeners on, the tracer clean, and the counters' readings
    before the test."""
    from predictionio_tpu.utils import tracing

    tracing.TRACER.reset()
    compilecache.enable()
    yield {"seconds": dict(compilecache._M_SECONDS.items()),
           "programs": dict(compilecache._M_PROGRAMS.items())}
    tracing.TRACER.reset()


def _moved(counter, before):
    return {k: v - before.get(k, 0.0) for k, v in counter.items()
            if v != before.get(k, 0.0)}


def _compile_spans(tree):
    return [s for s in tree if s["name"].startswith("compile.")]


def _fresh_jit(salt=1.5):
    """A program no test has traced, with a jit INSIDE it."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) * salt

    @jax.jit
    def outer(x):
        return inner(x).sum() + jnp.tanh(x).sum()

    return outer


X = np.arange(8, dtype=np.float32)


def test_first_call_under_a_verb_leaves_three_stages_a_second_none(record):
    from predictionio_tpu.utils import tracing

    f = _fresh_jit()
    with tracing.verb("unit.verb"):
        with tracing.span("unit.cold") as cold:
            f(X).block_until_ready()
        with tracing.span("unit.warm") as warm:
            f(X).block_until_ready()
    tree = tracing.last_verb("unit.verb")
    stages = _compile_spans(tree)
    # a jit inside a jit is part of the outer one's trace: one span a
    # stage, the program's
    assert [s["name"] for s in stages] == [
        "compile.trace", "compile.lower", "compile.backend"]
    assert {s["parentId"] for s in stages} == {cold.span_id}
    assert warm.span_id not in {s["parentId"] for s in tree}
    assert all("outer" in s["attrs"]["program"] for s in stages)
    assert "cache" not in stages[0]["attrs"]
    assert stages[2]["attrs"]["cache"] in ("miss", "off")
    assert stages[2]["attrs"]["cache_read_s"] == 0.0
    for a, b in zip(stages, stages[1:]):      # they add: none overlaps
        assert a["endNs"] <= b["startNs"]
    root = tree[0]["attrs"]
    assert set(compilecache.ROOT_SUMS) <= set(root)
    assert (root["programs_traced"], root["programs_lowered"],
            root["programs_compiled"], root["cache_hits"]) == (1, 1, 1, 0)
    for key, s in zip(("trace_s", "lower_s", "compile_s"), stages):
        assert root[key] == pytest.approx(
            (s["endNs"] - s["startNs"]) / 1e9, abs=1e-9)
    assert root["cache_load_s"] == 0.0
    line = compilecache.compile_line(root)
    assert line.startswith("compile: traced 1 (") and \
        "compiled 1 (" in line and line.endswith("cache answered 0 (0.0 s)")
    # the registry counted the same three, once each
    assert sorted(k[0] for k in _moved(compilecache._M_PROGRAMS,
                                       record["programs"])) == [
        "compile", "lower", "trace"]


def test_a_warm_verb_has_no_compile_span_and_no_sums(record):
    from predictionio_tpu.utils import tracing

    f = _fresh_jit(2.5)
    f(X).block_until_ready()
    with tracing.verb("unit.verb", who="warm"):
        with tracing.span("unit.step"):
            f(X).block_until_ready()
    tree = tracing.last_verb("unit.verb")
    assert [s["name"] for s in tree] == ["unit.verb", "unit.step"]
    assert tree[0]["attrs"] == {"who": "warm"}
    assert compilecache.compile_line(tree[0]["attrs"]) is None


def test_outside_a_verb_no_span_is_made_and_the_registry_counts(record):
    from predictionio_tpu.utils import tracing

    _fresh_jit(3.5)(X).block_until_ready()
    assert tracing.TRACER.last_verbs == {} and len(tracing.TRACER.ring) == 0
    seconds = _moved(compilecache._M_SECONDS, record["seconds"])
    assert set(seconds) == {("trace",), ("lower",), ("compile",)}
    assert all(v > 0 for v in seconds.values())
    programs = _moved(compilecache._M_PROGRAMS, record["programs"])
    assert {k[0]: v for k, v in programs.items()} == {
        "trace": 1, "lower": 1, "compile": 1}
    from predictionio_tpu.utils.metrics import REGISTRY

    text = REGISTRY.render()
    assert 'pio_compile_seconds_total{stage="trace"}' in text
    assert 'pio_compile_programs_total{stage="compile",cache="' in text


def test_stages_on_two_threads_are_kept_apart(record):
    """The depth is the thread's: a program compiled on a bound thread
    while this one is inside a trace is recorded, under ITS span."""
    import threading

    from predictionio_tpu.utils import tracing

    other = _fresh_jit(4.5)
    seen = {}

    def elsewhere():
        with tracing.span("unit.pooled") as sp:
            seen["id"] = sp.span_id
            other(X).block_until_ready()

    @jax.jit
    def slow_to_trace(x):
        t = threading.Thread(target=tracing.bind_current(elsewhere))
        t.start()
        t.join(timeout=120)
        assert not t.is_alive()
        return jnp.cos(x).sum()

    with tracing.verb("unit.verb"):
        slow_to_trace(X).block_until_ready()
    tree = tracing.last_verb("unit.verb")
    pooled = [s for s in _compile_spans(tree)
              if s["parentId"] == seen["id"]]
    assert [s["name"] for s in pooled] == [
        "compile.trace", "compile.lower", "compile.backend"]
    assert tree[0]["attrs"]["programs_traced"] == 2
    assert tree[0]["attrs"]["programs_compiled"] == 2


def test_cleared_caches_over_a_cache_directory_read_a_hit(
        record, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from predictionio_tpu.utils import tracing

    f = _fresh_jit(5.5)
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        with tracing.verb("unit.verb"):
            f(X).block_until_ready()
        cold = tracing.last_verb("unit.verb")
        jax.clear_caches()
        with tracing.verb("unit.verb"):
            f(X).block_until_ready()
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
        cc.reset_cache()       # the fixture above puts the directory back
    assert cold[0]["attrs"]["cache_misses"] == 1
    assert [s["attrs"]["cache"] for s in _compile_spans(cold)
            if s["name"] == "compile.backend"] == ["miss"]
    assert tracing.first_verb("unit.verb") == cold
    tree = tracing.last_verb("unit.verb")
    (backend,) = [s for s in tree if s["name"] == "compile.backend"]
    assert backend["attrs"]["cache"] == "hit"
    assert 0 < backend["attrs"]["cache_read_s"] <= \
        (backend["endNs"] - backend["startNs"]) / 1e9
    root = tree[0]["attrs"]
    assert (root["cache_hits"], root["cache_misses"],
            root["programs_compiled"]) == (1, 0, 0)
    assert root["programs_traced"] == root["programs_lowered"] == 1
    assert root["compile_s"] == 0.0 < root["cache_load_s"]
    assert "compiled 0 (0.0 s), cache answered 1 (" in \
        compilecache.compile_line(root)
    assert _moved(compilecache._M_PROGRAMS, record["programs"])[
        ("cache_load", "hit")] == 1
