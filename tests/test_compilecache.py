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
