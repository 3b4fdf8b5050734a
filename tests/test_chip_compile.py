"""The main path's kernels and programs, held to the CHIP's compiler.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (``jax.experimental.topologies``): what it
refuses here it would refuse on the v5e, at no chip time. Interpret
mode and ``jax.export`` lowering (tests/test_ops.py TestTPULowering)
cannot show this — the fused gather→Gram kernel passed both and was
refused by Mosaic for its ``1 × k`` row slices until it gathered
128-lane lines.

Everything that touches the topology lives in the module-scoped
fixtures below — never at import, in a ``skipif`` or in
``parametrize`` — and in this ONE file: only one process may load the
TPU library, and pytest-xdist hands a file to one worker.

A compile that passes is not a chip run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sds_tree(tree, sharding_of):
    """Host arrays → ShapeDtypeStructs (lowering needs only avals)."""
    def one(a):
        a = np.asarray(a)
        return _sds(a.shape, a.dtype, sharding_of(a))

    return jax.tree.map(one, tree)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# -- the three kernels at the widths of ML-20M, rank 64 ------------------------


def test_chol_solve_pallas(one_chip):
    from predictionio_tpu.ops.cholesky import chol_solve_pallas

    c = jax.jit(chol_solve_pallas).lower(
        _sds((4096, 64, 64), jnp.float32, one_chip),
        _sds((4096, 64), jnp.float32, one_chip)).compile()
    assert _has_kernel(c)


def test_score_topk(one_chip):
    import functools

    from predictionio_tpu.ops.topk import score_topk

    c = jax.jit(functools.partial(score_topk, k=16, tile=2048,
                                  n_valid=26_744)).lower(
        _sds((64, 64), jnp.float32, one_chip),
        _sds((27_136, 64), jnp.float32, one_chip)).compile()
    assert _has_kernel(c)


def _gram_route(compiled) -> str:
    """Which way the compiled ``gather_gram`` dispatch fetches its
    factor lines, read off the custom call: only a dispatch that keeps
    the table in VMEM asks the compiler for ``_VMEM_LIMIT`` of it."""
    from predictionio_tpu.ops.gram import _VMEM_LIMIT

    asked = ('"scoped_memory_configs":[{"memory_space":"1","offset":"0",'
             f'"size":"{_VMEM_LIMIT}"}}]')
    return "resident" if asked in compiled.as_text() else "copied"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("C", [128, 512, 2048, 8192])
def test_gather_gram(one_chip, monkeypatch, C, dtype):
    """Rank 64 at every gathered ladder width, against the four factor
    tables of the benchmark's two ALS cells (26,744 items; 138,493
    users padded to the solve chunk; Last.fm's 270,016 and 294,016):
    6.8, 35.5, 69.1 and 75.3 MB of lines. By the rule
    (``table_is_resident``) all four are held in VMEM for the dispatch
    (PR 41: a line is a vector load from a (lines, 128) scratch that
    the first program fills with one copy, under a 96 MiB limit) — and
    the copy route, which a larger table takes, is held to the
    compiler beside it on the largest: since PR 37 the pipelined
    kernel, two (T, 128) tile buffers, waits through stand-in
    descriptors of up to T lines, and two (8, C) index blocks in SMEM
    — 512 KB at C = 8192, which the v5e's compiler takes."""
    from predictionio_tpu.ops import gram

    def compiled(n_other):
        # a fresh function: the rule's constant is not part of a
        # cached trace's key
        c = jax.jit(lambda *a: gram.gather_gram(*a)).lower(
            _sds((n_other, 64), dtype, one_chip),
            _sds((304, C), jnp.int32, one_chip),
            _sds((304, C), jnp.float32, one_chip),
            _sds((304, C), jnp.float32, one_chip),
            _sds((304,), jnp.int32, one_chip)).compile()
        assert _has_kernel(c)
        return c

    for n_other in (26_744, 138_496, 270_016, 294_016):
        assert gram.table_is_resident(n_other, 64)
        assert _gram_route(compiled(n_other)) == "resident"
    monkeypatch.setattr(gram, "_RESIDENT_TABLE_BYTES",
                        gram.table_bytes(294_016, 64) - 1)
    assert _gram_route(compiled(294_016)) == "copied"


# -- the programs around them -------------------------------------------------


@pytest.mark.parametrize("B", [1, 2, 4, 8, 16, 32, 64])
def test_serving_program_every_default_bucket(one_chip, B):
    """gather → score → top-k at each bucket of the default AOT ladder
    (the XLA scorer: ``ResidentScorer._pallas_for`` keeps the streaming
    kernel for B·n_items > 64M)."""
    from predictionio_tpu.models import als
    from predictionio_tpu.server.aot import BucketLadder

    assert B in list(BucketLadder.parse("auto", 64))
    als._gather_score_topk_jit().lower(
        _sds((138_493, 64), jnp.float32, one_chip),
        _sds((27_136, 64), jnp.float32, one_chip),
        _sds((B,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        k=16, n_valid=26_744, pallas=False, tile=2048).compile()


@pytest.fixture(scope="module")
def ml20m_shape_coo():
    """The ML-20M catalog (138,493 × 26,744) at a reduced nnz."""
    from predictionio_tpu.models.als import RatingsCOO

    rng = np.random.default_rng(7)
    nnz = 200_000
    users = (rng.zipf(1.35, size=nnz) % 138_493).astype(np.int32)
    items = (rng.zipf(1.25, size=nnz) % 26_744).astype(np.int32)
    ratings = (rng.integers(1, 11, size=nnz) * 0.5).astype(np.float32)
    return RatingsCOO(users, items, ratings, 138_493, 26_744)


@pytest.mark.parametrize("gram_mode", ["pallas", "off"])
def test_als_step_single_device(one_chip, ml20m_shape_coo, gram_mode):
    """One ALS iteration at rank 64 as `pio train` builds it. Code that
    asks ``jax.default_backend()`` sees the CPU here, so the platform
    and the Gram mode a TPU resolves to are passed in."""
    from predictionio_tpu.models import als
    from predictionio_tpu.utils.opcount import _host_side_bufs

    prep = als.als_prepare(ml20m_shape_coo)
    train = als._compiled_bucketed(
        prep.u_side.geometry, prep.i_side.geometry, prep.n_users,
        prep.n_items, 64, 1, False, True, "tpu", False, "high", gram_mode)
    args = _sds_tree(
        (_host_side_bufs(prep.u_side), _host_side_bufs(prep.i_side),
         np.zeros((prep.n_items, 64), np.float32),
         np.float32(0.05), np.float32(1.0)),
        lambda a: one_chip)
    c = train.lower(*args).compile()
    assert _has_kernel(c) == (gram_mode == "pallas")


def test_als_step_sharded_four_chips(topo, ml20m_shape_coo):
    """The program `pio train` takes on a four-chip host (default
    meshConf → als_train_sharded), fused mode, on a mesh of the
    described devices; per-device memory must fit the chip."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.models import als_sharded

    n_dev = len(topo.devices)
    assert n_dev == 4
    mesh = Mesh(np.array(topo.devices), ("data",))
    sprep = als_sharded.als_prepare_sharded(ml20m_shape_coo, n_dev)
    train = als_sharded._compiled_sharded(
        mesh, sprep.geom_u, sprep.geom_i, 64, 1, False, True,
        gram_mode="pallas")

    def sharding_of(a):
        # stacked layouts lead with the device axis
        return NamedSharding(mesh, P("data") if a.ndim >= 1
                             and a.shape[0] == n_dev else P())

    args = _sds_tree(
        (sprep._stacked(sprep.u_sides), sprep._stacked(sprep.i_sides)),
        sharding_of) + (
        _sds((sprep.block_i * n_dev, 64), jnp.float32,
             NamedSharding(mesh, P("data", None))),
        _sds((), jnp.float32, NamedSharding(mesh, P())),
        _sds((), jnp.float32, NamedSharding(mesh, P())))
    c = train.lower(*args).compile()
    assert _has_kernel(c) and "all-gather" in c.as_text()
    mem = c.memory_analysis()
    per_device = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_device < 16e9


def _ragged_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("held", [8, 1])
def test_expert_dispatch_at_published_widths(one_chip, held):
    """``ops/moe_dispatch`` for one chip's share of GLM-4.7-Flash's
    expert layer — 16,384 tokens, top-4 of 64, 2048 × 1536 experts,
    the 65,536-row pair buffer — forward and backward: the grouped
    products must be the compiler's own ragged-dot kernels (a dense
    expansion over the groups would be ``held`` times the work), for
    the lhs AND the weights' gradients."""
    from predictionio_tpu.ops import moe_dispatch

    T, d, f, E, k = 16384, 2048, 1536, 64, 4

    def layer(x, scores, wg, wu, wd):
        ids, gates = moe_dispatch.route(scores, jnp.zeros(E), k, 1.8)
        p = moe_dispatch.plan(ids, tuple(range(held)), E)
        return moe_dispatch.experts_swiglu(x, wg, wu, wd, gates, p)

    args = (_sds((T, d), jnp.bfloat16, one_chip),
            _sds((T, E), jnp.float32, one_chip),
            _sds((held, d, f), jnp.bfloat16, one_chip),
            _sds((held, d, f), jnp.bfloat16, one_chip),
            _sds((held, f, d), jnp.bfloat16, one_chip))
    fwd = jax.jit(layer).lower(*args).compile()
    assert _ragged_calls(fwd) >= 3
    bwd = jax.jit(jax.grad(
        lambda *a: layer(*a).sum(), (0, 1, 2, 3, 4))).lower(*args).compile()
    assert _ragged_calls(bwd) >= 9
    assert bwd.memory_analysis().temp_size_in_bytes < 3e9


def test_attention_block_at_published_widths(one_chip):
    """One sequence's causal, segment-masked attention at 20 heads of
    256 over 4,096 positions, forward and backward: the three kernels
    of ops/seq_attention, chosen because the program is lowered FOR a
    TPU (this process is a CPU one)."""
    from predictionio_tpu.models import glm4_moe_lite as glm

    c = glm.GlmConfig()
    S, H, D = c.seq_len, c.num_attention_heads, c.qk_head_dim
    qkv = _sds((S, H, D), jnp.bfloat16, one_chip)
    seg = _sds((S,), jnp.int32, one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, seg: glm._attention(q, k, v, seg, c).astype(
            jnp.float32).sum(),
        (0, 1, 2))).lower(qkv, qkv, qkv, seg).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # no score block in HBM (20 × 512 × 4,096 float32 was 0.17 GB, and
    # the sequence's 20 × 4,096 × 4,096 would be 1.3 GB): the rows'
    # statistics in their 128 lanes and the cotangents only
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_attention_at_the_sample_widths(one_chip, dtype):
    """The benchmark's ``sample`` configuration on a real chip: heads
    16 (keys) and 8 (values) wide, narrower than a lane tile, 64 slots
    in tiles of 32 rows. The same three kernels take them — there is no
    dense path to fall back to."""
    from predictionio_tpu.ops import seq_attention

    S, H = 64, 2
    q, v = (_sds((S, H, D), jnp.dtype(dtype), one_chip) for D in (16, 8))
    compiled = jax.jit(jax.grad(
        lambda q, k, v, seg: seq_attention.segment_attention(
            q, k, v, seg, 32, 64, 0.25).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, q, v, _sds((S,), jnp.int32, one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


# -- the lfm2_moe backbone at published widths ---------------------------------


def _lfm2_cell_config():
    """The benchmark's ``seqrec-lfm2-8b-a1b-ep4`` as the template
    builds it: the configuration's published keys and its job."""
    import json
    import os

    from predictionio_tpu.models import lfm2_moe as lfm

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "seqrec-lfm2-8b-a1b-ep4.json")
    with open(path) as f:
        conf = json.load(f)
    arch = {k: v for k, v in conf.items()
            if k in lfm.Lfm2Config.known_keys()}
    return lfm.Lfm2Config.from_architecture(dict(arch, **conf["job"]))


def test_grouped_query_attention_at_published_widths(one_chip):
    """One sequence's attention at 32 query heads over 8 key-value
    heads of 64, 4,096 positions, forward and backward: the same three
    kernels, a key-value head fetched for its four query heads."""
    from predictionio_tpu.models import seq_backbone

    c = _lfm2_cell_config()
    S, H, Hkv, D = (c.seq_len, c.num_attention_heads,
                    c.num_key_value_heads, c.head_dim)
    assert (H, Hkv, D) == (32, 8, 64)
    q = _sds((S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((S, Hkv, D), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, seg: seq_backbone.attention(
            q, k, v, seg, c, 0.125).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, kv, kv, _sds((S,), jnp.int32,
                                         one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # the rows' statistics in their 128 lanes (32 × 4,096 × 128 float32
    # = 67 MB each) and the cotangents; the sequence's scores would be
    # 2.1 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


# -- the smallthinker backbone at published widths -----------------------------


def _smallthinker_cell_config():
    """The benchmark's ``seqrec-smallthinker-21b-ep8`` as the template
    builds it: the configuration's published keys and its job."""
    import json
    import os

    from predictionio_tpu.models import smallthinker as st

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "seqrec-smallthinker-21b-ep8.json")
    with open(path) as f:
        conf = json.load(f)
    arch = {k: v for k, v in conf.items()
            if k in st.SmallThinkerConfig.known_keys()}
    return st.SmallThinkerConfig.from_architecture(dict(arch, **conf["job"]))


@pytest.mark.parametrize("windowed", [True, False],
                         ids=["window", "global"])
def test_long_sequence_attention_at_published_widths(one_chip, windowed):
    """One sequence's attention at 28 query heads over 4 key-value
    heads of 128 and 16,384 positions, forward and backward, with the
    window of 4,096 keys and without: a key-value head's keys and values
    are 4 MB each in VMEM, and dk/dv — whose seven query heads' rows
    (59 MB with their cotangents) would not fit — walks the query heads
    and leaves their float32 parts to be summed."""
    from predictionio_tpu.models import seq_backbone

    c = _smallthinker_cell_config()
    S, H, Hkv, D = (c.seq_len, c.num_attention_heads,
                    c.num_key_value_heads, c.head_dim)
    assert (S, H, Hkv, D, c.window) == (16384, 28, 4, 128, 4096)
    q = _sds((S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((S, Hkv, D), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, seg: seq_backbone.attention(
            q, k, v, seg, c, D ** -0.5,
            c.window if windowed else None).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, kv, kv, _sds((S,), jnp.int32,
                                         one_chip)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # the rows' statistics in their 128 lanes (28 × 16,384 × 128 float32
    # = 235 MB) and the query heads' float32 dk, dv (2 × 235 MB); one
    # head's scores alone would be 1.07 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


def _sdar_cell_config():
    """The benchmark's ``seqrec-sdar-30b-a3b-ep8`` as the template
    builds it: the configuration's published keys and its job."""
    import json
    import os

    from predictionio_tpu.models import sdar_moe as sd

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "seqrec-sdar-30b-a3b-ep8.json")
    with open(path) as f:
        conf = json.load(f)
    arch = {k: v for k, v in conf.items() if k in sd.SdarConfig.known_keys()}
    return sd.SdarConfig.from_architecture(dict(arch, **conf["job"]))


def test_block_rule_attention_at_published_widths(one_chip):
    """Both streams of an 8,192-slot sequence — operands of 16,384 rows,
    32 query heads over 4 key-value heads of 128 — under the block
    rule, forward and all three gradients, for the described chip: the
    kernels with two intervals a row and two runs of tiles a block."""
    from predictionio_tpu.models import seq_backbone

    c = _sdar_cell_config()
    S, H, Hkv, D = (c.seq_len, c.num_attention_heads,
                    c.num_key_value_heads, c.head_dim)
    assert (S, H, Hkv, D, c.block_length) == (8192, 32, 4, 128, 4)
    q = _sds((2 * S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((2 * S, Hkv, D), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, seg: seq_backbone.block_attention(
            q, k, v, seg, c, D ** -0.5,
            c.block_length).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, kv, kv, _sds((S,), jnp.int32,
                                         one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "seq_attention_bd_fwd" in text and "seq_attention_bd_dkv" in text
    # the rows' statistics in their 128 lanes (32 × 16,384 × 128 float32
    # = 268 MB, twice), the query heads' float32 dk, dv (2 × 268 MB)
    # and the rows' intervals: 954 MB; one head's scores alone would be
    # 1.07 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def _qwen3next_cell_config():
    """The benchmark's ``seqrec-qwen3next-80b-a3b-ep16`` as the template
    builds it: the configuration's published keys and its job."""
    import json
    import os

    from predictionio_tpu.models import qwen3_next as qn

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "seqrec-qwen3next-80b-a3b-ep16.json")
    with open(path) as f:
        conf = json.load(f)
    arch = {k: v for k, v in conf.items()
            if k in qn.Qwen3NextConfig.known_keys()}
    return qn.Qwen3NextConfig.from_architecture(dict(arch, **conf["job"]))


#: rows of a chunk → the most scoped VMEM (bytes) the walk's forward and
#: reverse kernel may use: the cell's chunk of 64 rows (7.1 and 14.3 MB
#: of the chip's 16 MiB as its compiler counts them), and the least and
#: the next chunk ``gated_delta.walk_form`` sends to the kernels (a
#: ``gdn_chunk`` is a config field, 8 rows one tile of a float32: 3.0
#: and 7.7 MB, 3.7 and 8.7 MB — the state and its products' results do
#: not shrink with the chunk)
WALK_CHUNKS = {8: (4 << 20, 8 << 20), 16: (4 << 20, 9 << 20),
               64: (8 << 20, 15 << 20)}


@pytest.mark.parametrize("C", sorted(WALK_CHUNKS))
def test_the_delta_rules_walk_at_published_widths(one_chip, C):
    """The walk over one 2,048-row block of the Qwen3-Next cell — 32
    value heads, a 128 × 128 float32 state a head, 32 chunks of 64 rows
    (the cell's) or as many of 8 and 16 as the block holds (the
    shortest chunks the shape rule sends to the kernels) — forward
    (with the entering states kept) and in reverse, as ``jax.vjp`` of
    ``gated_delta._walk`` meets them: two Mosaic kernels, each INSIDE
    the default scoped VMEM — neither asks for a limit of its own
    (``scoped_memory_configs`` empty), which would change how XLA tiles
    the rest of the train program (PERF.md §6, PR 41)."""
    import re

    from predictionio_tpu.ops import gated_delta

    H, D = 32, 128
    M = gated_delta.BLOCK_ROWS // C
    assert gated_delta.walk_form(C, D, D) == "kernel"

    def f32(*shape):
        return _sds(shape, jnp.float32, one_chip)

    operands = (f32(H, D, D), f32(M, H, C, D), f32(M, H, 2 * C, D),
                f32(M, H, C, C), f32(M, H, C, D), f32(M, H))
    compiled = jax.jit(
        lambda d, *operands: jax.vjp(gated_delta._walk, *operands)[1](d)
    ).lower((f32(H, D, D), f32(M, H, C, D)), *operands).compile()
    text = compiled.as_text()
    used = {}
    for line in text.splitlines():
        name = re.match(r"\s*(?:ROOT )?%?(gdn_walk_\w+?)(?:\.\d+)? = ", line)
        if name:
            assert '"scoped_memory_configs":[]' in line, name.group(1)
            used[name.group(1)] = int(re.search(
                r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                r'"offset":"0","size":"(\d+)"', line).group(1))
    assert sorted(used) == ["gdn_walk_bwd", "gdn_walk_fwd"]
    forward, reverse = WALK_CHUNKS[C]
    assert used["gdn_walk_fwd"] < forward and used["gdn_walk_bwd"] < reverse
    # the kept entering states [M, 32, 128, 128] float32 and nothing
    # a chunk's products would leave behind
    assert compiled.memory_analysis().temp_size_in_bytes < (
        M * H * D * D * 4 + 0.04e9)


def _xing4_cell_config():
    """The benchmark's ``seqrec-xing4-29b-a4b-ep8`` as the template
    builds it: the configuration's published keys and its job."""
    import json
    import os

    from predictionio_tpu.models import xing4_0 as xg

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "seqrec-xing4-29b-a4b-ep8.json")
    with open(path) as f:
        conf = json.load(f)
    arch = {k: v for k, v in conf.items() if k in xg.XingConfig.known_keys()}
    return xg.XingConfig.from_architecture(dict(arch, **conf["job"]))


def test_latent_attention_at_xing4_widths(one_chip):
    """One sequence's causal, segment-masked attention at 32 heads of
    128 + 64 = 192 query/key dims beside 128 value dims over 4,096
    slots, forward and backward: ``ops/seq_attention``'s three kernels
    as they are — 192 is no multiple of the 128 lanes (GLM's heads are
    256 / 256), and Mosaic takes it: nothing is padded."""
    from predictionio_tpu.models import glm4_moe_lite as glm

    c = _xing4_cell_config()
    S, H = c.seq_len, c.num_attention_heads
    assert (S, H, c.qk_head_dim, c.v_head_dim) == (4096, 32, 192, 128)
    qk = _sds((S, H, c.qk_head_dim), jnp.bfloat16, one_chip)
    v = _sds((S, H, c.v_head_dim), jnp.bfloat16, one_chip)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, seg: glm._attention(q, k, v, seg, c).astype(
            jnp.float32).sum(),
        (0, 1, 2))).lower(qk, qk, v, _sds((S,), jnp.int32,
                                         one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    for kernel in ("seq_attention_fwd", "seq_attention_dq",
                   "seq_attention_dkv"):
        assert kernel in text
    # the rows' statistics in their 128 lanes (32 × 4,096 × 128 float32
    # = 67 MB, twice) and the cotangents: 0.34 GB; one head's scores
    # alone would be 67 MB, all 32 heads' 2.1 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9


# -- the backbones' whole train programs at published widths -------------------

#: cell → (its config, the parameters it pins, steps of a train, the
#: least grouped products, the most bytes of arguments and temporaries,
#: the kernels' calls: attention's forward, dq, dk/dv and the delta
#: rule's walk)
TRAIN_PROGRAMS = {
    # 507.8 M parameters, 16 steps of 8 × 4,096 slots, three scanned
    # bodies (conv + dense, attention + experts, 3 × conv + experts),
    # the tied head: forward, recomputation and backward of one
    # attention layer's three kernels, and of four expert layers' three
    # grouped products (since PR 51 the turn's recomputation holds none:
    # the experts' backward rule recomputes its blocks itself — 34
    # custom calls where the parent had 40, 10.27 GB of temporaries
    # where it had 11.82)
    "lfm2": (_lfm2_cell_config, 507_820_160, 16, 30, 6.2e9, 12.5e9,
             {"seq_attention_fwd": 2, "seq_attention_dq": 1,
              "seq_attention_dkv": 1}),
    # 370.5 M parameters, 16 steps of 2 × 16,384 slots, two scanned
    # bodies (1 × global, 3 × window), the untied head. The pair buffer
    # is 196,608 rows; since PR 51 its sorted side is walked a block of
    # 8,192 rows at a time as far as the held experts' rows reach, and
    # the program's temporaries are 11.07 GB where they were 15.07 (the
    # limit is the parent's line: it fitted by 1 GB). The GLOBAL turn
    # keeps the kernel's output and log-sum-exp (0.239 GB,
    # ``attn_kept_bytes``) and runs its forward once; the window run's
    # three layers would keep 0.716 GB more — which did not fit before
    # PR 51 and has not been tried since — and run theirs twice: three
    # forward calls, not four
    "smallthinker": (_smallthinker_cell_config, 370_547_200, 16, 30,
                     4.6e9, 15.3e9,
                     {"seq_attention_fwd": 3, "seq_attention_dq": 2,
                      "seq_attention_dkv": 2}),
    # 456.3 M parameters, 32 steps of one 8,192-slot sequence as two
    # streams, ONE scanned body of four layers, the noise drawn in the
    # step, the untied head on the noised rows. Every turn keeps the
    # kernel's output and log-sum-exp (0.545 GB over the four layers,
    # ``attn_kept_bytes``) and the body holds ONE forward call: 8.62 GB
    # of temporaries (8.82 before PR 51) where a plain turn read 9.08
    # (the second forward's output and its rows' statistics in their
    # 128 lanes, 0.40 GB a layer, are no longer made)
    "sdar": (_sdar_cell_config, 456_346_624, 32, 15, 5.6e9, 9.6e9,
             {"seq_attention_bd_fwd": 1, "seq_attention_bd_dq": 1,
              "seq_attention_bd_dkv": 1}),
    # 625.7 M parameters, 16 steps of one 16,384-slot sequence, two
    # scanned bodies (3 × linear, 1 × full), the expert half in four
    # chunks of 4,096 rows, the untied head; a linear layer's turn keeps
    # the recurrence's output and a state a 2,048-row block (0.86 GB
    # over the three: ``gdn_kept_bytes``): 12.10 GB of temporaries (the
    # updated state 7.5 and the gradients 2.5 among them; 12.18 before
    # PR 51), 3.8 GB under the chip. Since PR 53 the walk over a block's
    # chunks is its own kernels — forward at the rule's two forward
    # sites, in reverse once — and the chunks' solve the inverse times
    # the right-hand side; the chip's compiler reads the same bytes as
    # before them (temporaries 12,101,136,384 against 12,101,942,784,
    # arguments 7,512,296,960 either side): inside the rule's backward
    # a block's entering states [32, 32, 128, 128] are what the
    # differentiated scan kept too, so the bounds stay where they were
    "qwen3next": (_qwen3next_cell_config, 625_667_136, 16, 15, 7.6e9,
                  12.3e9,
                  {"seq_attention_fwd": 2, "seq_attention_dq": 1,
                   "seq_attention_dkv": 1, "gdn_walk_fwd": 2,
                   "gdn_walk_bwd": 1}),
    # 759.3 M parameters, 32 steps of one 4,096-slot sequence, two
    # scanned bodies (1 × dense, 4 × experts) whose carry is the FOUR-copy
    # stream [4, 1, 4096, 3584] float32 (235 MB a kept boundary, 1.17 GB
    # over the five turns); a turn recomputes coefficients and mixes.
    # The compiler updates the donated state IN PLACE here (the
    # temporaries, 9.27 GB, hold the gradients 3.04 and the activations,
    # not the state again): what the program holds at once is the
    # compiler's own ``peak_memory_in_bytes``, 15.005 GB (``PEAK``)
    "xing4": (_xing4_cell_config, 759_346_190, 32, 12, 9.2e9, 9.5e9,
              {"seq_attention_fwd": 4, "seq_attention_dq": 2,
               "seq_attention_dkv": 2}),
}
#: cell → the most bytes of arguments and temporaries TOGETHER, the
#: aliased state counted once (the compiler's ``peak_memory_in_bytes``),
#: where the temporaries do not hold the donated state again: ISSUE 50's
#: line, 16.0e9 B of the chip's 15.75 GiB
PEAK = {"xing4": 16.0e9}


@pytest.mark.parametrize("cell", sorted(TRAIN_PROGRAMS))
def test_train_program_at_published_widths(one_chip, cell):
    """A cell's whole train program, as ``seq_backbone.build`` makes it
    of the backbone's declaration — parameters with Adam's state, the
    router bias and the batches a train holds, all read from the
    backbone — for the described chip: the attention kernels and the
    grouped products are in it — the forward kernel twice a scanned
    body (the pass and the turn's recomputation) unless the body's
    turn keeps its output —, and it fits the chip's 16 GB."""
    import collections
    import re
    import types

    from predictionio_tpu.models import seq_backbone
    from predictionio_tpu.models.seq_rec import _make_tx

    config, n_params, steps, ragged, arguments, temporaries, attention = (
        TRAIN_PROGRAMS[cell])
    c = config()
    b = seq_backbone.backbone(c.model_type)
    assert b.n_params(c) == n_params
    params = jax.tree.map(lambda s: _sds(s, jnp.float32, one_chip),
                          b.param_shapes(c), is_leaf=seq_backbone._is_shape)
    opt = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip),
                       jax.eval_shape(_make_tx().init, params))
    bias = _sds(jax.eval_shape(lambda: b.init_state(c, 0))[1].shape,
                jnp.float32, one_chip)
    B = c.seqs_per_step
    data = {k: _sds((steps, B, c.seq_len), jnp.int32, one_chip)
            for k in b.train_keys}
    packed = types.SimpleNamespace(tokens=np.zeros((steps * B, 1), np.int32))
    for k, v in b.draws(packed, 0).items():     # what keys a noise
        data[k] = _sds((steps, B) + v.shape[1:], v.dtype, one_chip)
    compiled = b.train_program(c, 1).lower((params, opt, bias),
                                           data).compile()
    assert _ragged_calls(compiled) >= ragged
    # a kernel's custom call is named after it: ``%seq_attention_fwd.3 =``
    assert collections.Counter(re.findall(
        r"(?m)^\s*%?((?:seq_attention|gdn_walk)_\w+?)(?:\.\d+)? = ",
        compiled.as_text())) == attention
    mem = compiled.memory_analysis()
    # the donated state is counted in the arguments AND (updated) in
    # the temporaries: what the program holds at once is the latter
    assert mem.argument_size_in_bytes < arguments
    assert mem.temp_size_in_bytes < temporaries
    if cell in PEAK:
        assert mem.peak_memory_in_bytes < PEAK[cell]
