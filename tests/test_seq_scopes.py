"""The ``seqrec.*`` scopes of the backbones' train programs (PR 36):
which names a lowered program carries and where, and that the
program's identity in JAX's persistent compilation cache changes with
them — the cache strips debug info before it hashes a module, and a
scope lives only there. And the seam the backbones share (PR 43): what
``seq_backbone.build`` makes of a declaration, once, for each entry of
the table.
"""

import contextlib
import dataclasses
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from predictionio_tpu.models import glm4_moe_lite as glm
from predictionio_tpu.models import lfm2_moe as lfm
from predictionio_tpu.models import qwen3_next as qn
from predictionio_tpu.models import sdar_moe as sd
from predictionio_tpu.models import seq_backbone
from predictionio_tpu.models import smallthinker as st
from predictionio_tpu.models import xing4_0 as xg
from predictionio_tpu.models.seq_rec import _make_tx
from tests.kernel_calls import kernel_calls

TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            ep_size=1, num_experts_per_tok=2, vocab_size=50, seq_len=64,
            seqs_per_step=2, attn_block=32, token_chunk=64, init_std=0.2)
BACKBONES = {
    "glm4_moe_lite": (glm, glm.GlmConfig.from_architecture(dict(
        TINY, model_type="glm4_moe_lite", num_attention_heads=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
        num_hidden_layers=3))),
    "lfm2_moe": (lfm, lfm.Lfm2Config.from_architecture(dict(
        TINY, model_type="lfm2_moe", num_attention_heads=4,
        num_key_value_heads=2, conv_L_cache=3, conv_bias=False,
        use_expert_bias=True, num_hidden_layers=4, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv", "conv"],
        num_experts=8))),
    "smallthinker": (st, st.SmallThinkerConfig.from_architecture(dict(
        {k: v for k, v in TINY.items()
         if k not in ("intermediate_size", "moe_intermediate_size",
                      "num_experts_per_tok")},
        model_type="smallthinker", head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, moe_ffn_hidden_size=32,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        num_hidden_layers=4, sliding_window_layout=[0, 1, 1, 1],
        rope_layout=[0, 1, 1, 1], sliding_window_size=24))),
    "sdar_moe": (sd, sd.SdarConfig.from_architecture(dict(
        {k: v for k, v in TINY.items() if k != "intermediate_size"},
        model_type="sdar_moe", head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, num_experts=8, num_hidden_layers=2,
        block_length=4))),
    "qwen3_next": (qn, qn.Qwen3NextConfig.from_architecture(dict(
        {k: v for k, v in TINY.items() if k != "intermediate_size"},
        model_type="qwen3_next", head_dim=32, num_attention_heads=4,
        num_key_value_heads=2, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, shared_expert_intermediate_size=32,
        num_experts=8, num_hidden_layers=4, gdn_chunk=16))),
    "xing4_0": (xg, xg.XingConfig.from_architecture(dict(
        TINY, model_type="xing4_0", num_attention_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_hidden_layers=3,
        first_k_dense_replace=1, num_nextn_predict_layers=1))),
}
#: what this PR added: around and beside the operators' scopes
NEW = {"seqrec.step", "seqrec.stack", "seqrec.stack.cast", "seqrec.norm",
       "seqrec.residual"}
#: the scopes not every backbone opens (``smallthinker``'s global layers
#: are grouped-query attention like ``lfm2_moe``'s; PR 38)
OWN = {"glm4_moe_lite": {"seqrec.mla", "seqrec.mla.attention", "seqrec.mtp"},
       "lfm2_moe": {"seqrec.conv", "seqrec.conv.mix", "seqrec.gqa",
                    "seqrec.gqa.attention"},
       "smallthinker": {"seqrec.swa", "seqrec.swa.attention", "seqrec.gqa",
                        "seqrec.gqa.attention"},
       "sdar_moe": {"seqrec.bd", "seqrec.bd.attention", "seqrec.bd.noise"},
       "qwen3_next": {"seqrec.gdn", "seqrec.gdn.conv", "seqrec.gdn.scan",
                      "seqrec.gqa", "seqrec.gqa.attention"},
       # GLM's operators under GLM's names, and the stream's own (PR 50)
       "xing4_0": {"seqrec.mla", "seqrec.mla.attention", "seqrec.mtp",
                   "seqrec.mhc", "seqrec.mhc.coef", "seqrec.mhc.mix"}}
#: a scope every other backbone opens and this one has nothing for: no
#: dense feed-forward layer and no shared expert
#: — or whose expert branch's residual add IS the mixer's write-back
LACKS = {"smallthinker": {"seqrec.ffn"}, "sdar_moe": {"seqrec.ffn"},
         "xing4_0": {"seqrec.residual"}}
SCOPE = re.compile(r"seqrec\.[a-z_.]+[a-z_]")    # scope_reduce's pattern


def _abstract_args(module, c):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    backbone = module.BACKBONE
    params = jax.tree.map(sds, backbone.param_shapes(c),
                          is_leaf=seq_backbone._is_shape)
    _, bias = jax.eval_shape(lambda: backbone.init_state(c, 0))
    data = {k: sds((2, c.seqs_per_step, c.seq_len), jnp.int32)
            for k in backbone.train_keys}
    if hasattr(module, "draws"):       # what keys the backbone's noise
        data["draw"] = sds((2, c.seqs_per_step, 2), jnp.uint32)
    return (params, jax.eval_shape(_make_tx().init, params), bias), data


def _program(module, c):
    """The backbone's train program, traced anew (the built
    ``train_program`` keeps one per config)."""
    return module.BACKBONE.train_program.__wrapped__(c, 1)


@contextlib.contextmanager
def _cache_in(directory):
    """JAX's persistent compilation cache in ``directory``, every
    program kept; the process's own settings put back after."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (str(directory), True, 0.0, 0)):
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        yield
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module", params=sorted(BACKBONES))
def compiled(request, tmp_path_factory):
    """A backbone's train program at a tiny size, compiled once (into a
    cache directory of its own: nothing stale can answer): the
    module's name and the ``op_name`` paths of its operations — what a
    device trace shows as ``tf_op`` — that hold a scope."""
    module, c = BACKBONES[request.param]
    with _cache_in(tmp_path_factory.mktemp("cache")):
        text = _program(module, c).lower(
            *_abstract_args(module, c)).compile().as_text()
    paths = {p for p in re.findall(r'op_name="([^"]*)"', text)
             if SCOPE.search(p)}
    return {"backbone": request.param, "paths": paths,
            "module": re.match(r"HloModule (\w+)", text).group(1),
            "found": {s for p in paths for s in SCOPE.findall(p)}}


# -- (a) the names and where they lie ----------------------------------------


@pytest.mark.parametrize("scope", sorted(seq_backbone.SCOPES))
def test_the_program_names_the_scope(compiled, scope):
    """Every new scope and every scope the program had before — and
    none of the other backbone's."""
    other = (set().union(*OWN.values()) - OWN[compiled["backbone"]]
             | LACKS.get(compiled["backbone"], set()))
    assert (scope in compiled["found"]) == (scope not in other)


def test_the_table_holds_every_name_the_program_opens(compiled):
    """``moe_dispatch`` opens its scopes itself, past ``scope()``'s
    check: the table that the program's name is made from lists them
    too."""
    assert compiled["found"] <= seq_backbone.SCOPES
    assert (NEW - LACKS.get(compiled["backbone"], set())
            | OWN[compiled["backbone"]]) <= compiled["found"]


def test_a_new_scope_lies_around_or_beside_the_old_ones(compiled):
    """The device trace's reader gives an operation to the INNERMOST
    scope of its path: a new scope inside an old one would take
    operations out of a metric that is already read."""
    innermost = set()
    for path in compiled["paths"]:
        # (an interpreted Pallas call repeats its caller's path; an
        # operation of an inner function may carry the path from THAT
        # function's top only)
        scopes = SCOPE.findall(path[max(path.rfind("jit(train"), 0):])
        old_seen = False
        for s in scopes:
            assert not (old_seen and s in NEW), path
            old_seen = old_seen or s not in NEW
        if "seqrec.step" in scopes:
            assert scopes.index("seqrec.step") == 0, path
            if scopes[-1] in ("seqrec.stack.cast", "seqrec.norm",
                              "seqrec.residual"):
                assert "seqrec.stack" in scopes[:-1], path
        innermost.add(scopes[-1])
    # each name is some operation's innermost: a metric has seconds to read
    assert NEW - LACKS.get(compiled["backbone"], set()) <= innermost
    if compiled["backbone"] == "xing4_0":     # and so has the mixer's each
        assert {"seqrec.mhc", "seqrec.mhc.coef", "seqrec.mhc.mix"} <= innermost


def test_the_backward_pass_lands_under_the_same_names(compiled):
    """(Not ``seqrec.residual``: an add's cotangent is no operation.)"""
    backward = {SCOPE.findall(p)[-1] for p in compiled["paths"]
                if "transpose(jvp(seqrec.stack))" in p}
    assert {"seqrec.stack", "seqrec.stack.cast", "seqrec.norm"} <= backward


def test_scope_refuses_a_name_the_table_lacks():
    with seq_backbone.scope("seqrec.norm"):
        pass
    with pytest.raises(ValueError, match="seqrec.nrom"):
        seq_backbone.scope("seqrec.nrom")


# -- (b) the program's identity carries the scope set -------------------------


def test_the_programs_name_is_made_from_the_scope_set(compiled, monkeypatch):
    name = seq_backbone.program_name()
    assert re.fullmatch(r"train_[0-9a-f]{8}", name)
    assert compiled["module"] == f"jit_{name}"
    monkeypatch.setattr(seq_backbone, "SCOPES",
                        seq_backbone.SCOPES - {"seqrec.norm"})
    assert seq_backbone.program_name() != name


class _NoScope(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _compiled_text(monkeypatch, old_scopes_only: bool) -> str:
    """The tiny ``lfm2_moe`` train program's compiled HLO, through the
    persistent cache; ``old_scopes_only``: this PR's scopes are no-ops
    and the table lacks them — the program of the parent commit."""
    module, c = BACKBONES["lfm2_moe"]
    with monkeypatch.context() as m:
        if old_scopes_only:
            old = seq_backbone.SCOPES - NEW

            def scope(name):
                return jax.named_scope(name) if name in old else _NoScope()

            m.setattr(seq_backbone, "SCOPES", old)
            for mod in (seq_backbone, module):
                m.setattr(mod, "scope", scope)
        return _program(module, c).lower(
            *_abstract_args(module, c)).compile().as_text()


def _train_entries(directory):
    return sorted(f.rsplit("-", 2)[0] for f in os.listdir(directory)
                  if f.startswith("jit_train"))


def test_a_program_with_new_scopes_is_a_new_cache_entry(tmp_path,
                                                        monkeypatch):
    """A cache directory the parent's program filled: the program as
    committed misses it once, its executable names the new scopes, and
    the next trace of it hits that entry with the names in it."""
    with _cache_in(tmp_path):
        parent = _compiled_text(monkeypatch, old_scopes_only=True)
        assert "seqrec.conv" in parent and "seqrec.stack" not in parent
        assert len(_train_entries(tmp_path)) == 1
        first = _compiled_text(monkeypatch, old_scopes_only=False)
        assert "seqrec.stack" in first and "seqrec.norm" in first
        entries = _train_entries(tmp_path)
        assert f"jit_{seq_backbone.program_name()}" in entries
        assert len(entries) == 2
        again = _compiled_text(monkeypatch, old_scopes_only=False)
        assert "seqrec.stack" in again
        assert _train_entries(tmp_path) == entries


def test_under_one_name_the_cache_answers_with_the_old_scopes(tmp_path,
                                                              monkeypatch):
    """The trap itself, in this JAX: the cache's key is taken after
    debug info is stripped, so with a name that ignores the scope set
    the program as committed is answered with the parent's executable
    and a trace of it would show the parent's names. (Should a later
    JAX hash the scopes, this fails and ``program_name`` can go.)"""
    monkeypatch.setattr(seq_backbone, "program_name", lambda: "train")
    with _cache_in(tmp_path):
        parent = _compiled_text(monkeypatch, old_scopes_only=True)
        stale = _compiled_text(monkeypatch, old_scopes_only=False)
        assert "seqrec.stack" not in parent
        assert "seqrec.stack" not in stale
        assert _train_entries(tmp_path) == ["jit_train"]


#: the accepted backbones' train programs at the sizes above, lowered:
#: SHA-256 of the text with the program's name (a digest of the scope
#: table) and the counters behind its private functions' names
#: (``@closed_call_757``: what ELSE the process lowered moves them, and
#: a ``checkpoint_name`` does) taken out. Four as PR 51 left them: it
#: changed what they SHARE (``moe_dispatch.experts_swiglu`` walks the
#: pair buffer block by block) — a change to shared code for ANOTHER
#: backbone leaves them text for text; ``qwen3_next`` as PR 53 left it
#: (its own ``ops/gated_delta.py``: the chunks' solve is the inverse
#: times the right-hand side; the walk's kernels are for whole tiles,
#: and this toy's 16 × 16 state walks under the ``lax.scan`` it had)
LOWERED = {"glm4_moe_lite": "22e46aadf4df5982",
           "lfm2_moe": "d79e90675dc9e8f7",
           "qwen3_next": "e2513e529b578f39",
           "smallthinker": "01e9af5f3b3604c1",
           "sdar_moe": "a477ea53ed464ee7"}


@pytest.mark.parametrize("model_type", sorted(LOWERED))
def test_an_accepted_backbones_program_lowers_as_before(model_type):
    """The rule of PRs 38, 40, 45 and 48: what a new backbone passes to
    shared code (a recurrence's chunk, a shared expert's gate, a leaf's
    own start), and what another backbone's turn keeps of a shared
    operator, leaves the accepted programs apart from their NAME as
    they were. (A JAX that prints another text moves all five.)"""
    module, c = BACKBONES[model_type]
    text = _program(module, c).lower(*_abstract_args(module, c)).as_text()
    name = seq_backbone.program_name()
    assert name in text
    text = re.sub(r"@(\w+?)_\d+\b", r"@\1", text.replace(name, "train"))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        LOWERED[model_type])


# -- (c) the seam: a backbone declares, ``build`` makes the rest --------------

#: what the benchmark reads of a backbone, by name
TEN_FIELDS = ("model_type", "config", "train", "sequence_logits",
              "next_item_scores", "heads", "batch_keys", "init_state",
              "n_params", "group_squares")
#: what ``build`` makes; a backbone module that defines one of them has
#: started the fifth copy
BUILT = ("n_params", "init_state", "_init_compiled", "grad_groups",
         "train_program", "_logits_compiled", "sequence_logits",
         "_next_compiled", "next_item_scores", "glm_train", "lfm2_train",
         "smallthinker_train", "sdar_train")
#: the bindings ``benchmark/`` reads by these names (Tentpole 4 of
#: ISSUE 43; they go with ROADMAP D9): each IS the built function
BINDINGS = {"glm4_moe_lite": {"n_params": "n_params",
                              "init_state": "init_state",
                              "sequence_logits": "sequence_logits",
                              "glm_train": "train"},
            "lfm2_moe": {"n_params": "n_params"},
            "smallthinker": {"n_params": "n_params"},
            "sdar_moe": {"n_params": "n_params"},
            "qwen3_next": {"n_params": "n_params"},
            "xing4_0": {}}


@pytest.mark.parametrize("model_type", sorted(seq_backbone._MODULES))
def test_a_backbone_is_its_declaration_built_once(model_type):
    assert set(BACKBONES) == set(seq_backbone._MODULES)
    module, c = BACKBONES[model_type]
    b = seq_backbone.backbone(model_type)
    assert b is module.BACKBONE
    assert b._fields[:10] == TEN_FIELDS
    assert (b.model_type, b.config) == (model_type, type(c))
    assert isinstance(c, seq_backbone.ArchitectureConfig)
    # an EQUAL config (another object) is answered by the same programs
    again = dataclasses.replace(c)
    assert again == c and again is not c
    assert b.train_program(c, 1) is b.train_program(again, 1)
    assert b.train_program(c, 1) is not b.train_program(c, 2)
    assert b.logits_program(c) is b.logits_program(again)
    assert b.next_program(c) is b.next_program(again)
    assert b.grad_groups(c) is b.grad_groups(again)
    assert b.n_params(c) == seq_backbone.count_params(b.param_shapes(c))
    # the module wrote none of it itself
    own = {name for name in BUILT if name in vars(module)}
    assert own == set(BINDINGS[model_type])
    for name, field in BINDINGS[model_type].items():
        assert getattr(module, name) is getattr(b, field)


# -- (c) the blocks of the pair buffer the experts ran (PR 51) -----------------


@pytest.mark.parametrize("model_type, ep_size", [
    *((m, 1) for m in sorted(BACKBONES)), ("glm4_moe_lite", 4)])
def test_the_fit_span_counts_the_blocks_the_experts_ran(model_type, ep_size,
                                                        monkeypatch):
    """A toy train of four steps on sequences without padding, a pair
    buffer of 256 rows in blocks of 64: with every expert held every
    block of every layer and step is run; with two of eight held, fewer
    — and no pair is dropped either way."""
    import numpy as np

    from predictionio_tpu.ops import moe_dispatch
    from predictionio_tpu.utils import tracing

    monkeypatch.setattr(moe_dispatch, "BLOCK_ROWS", 64)
    module, c = BACKBONES[model_type]
    # a config no other test trains: the built program is kept by config
    c = dataclasses.replace(c, ep_size=ep_size, init_std=0.21)
    histories = np.random.default_rng(0).integers(
        1, c.vocab_size - 1, (8, c.seq_len))    # sdar_moe: the last is MASK
    with tracing.verb("unit.train"):
        seq_backbone.train_histories(module.BACKBONE, histories, c, epochs=1,
                                     lr=1e-3, seed=0)
    fit = next(s for s in tracing.last_verb("unit.train")
               if s["name"] == "seqrec.fit")["attrs"]
    assert fit["moe_dropped_pairs"] == 0 and fit["losses_finite"]
    # no padding: every row of every layer's buffer holds a pair
    assert fit["moe_blocks"] * 64 == fit["moe_pairs"]
    if ep_size == 1:
        assert fit["moe_pairs_here"] == fit["moe_pairs"]
        assert fit["moe_blocks_run"] == fit["moe_blocks"]
    else:
        assert 0 < fit["moe_blocks_run"] < fit["moe_blocks"]
        assert fit["moe_blocks_run"] * 64 >= fit["moe_pairs_here"]


# -- (d) which layer turns keep attention's output (PR 48) --------------------

#: backbone → (the forward kernel, its calls in the train program's
#: jaxpr — two a call site, one a branch of ``platform_dependent`` —
#: and of those the ones a turn's RECOMPUTATION makes): every scanned
#: body that holds attention calls it in the forward pass; its turn's
#: recomputation calls it again unless the backbone's checkpoint line
#: keeps ``seq_attention.KEPT`` (``sdar_moe``: all layers;
#: ``smallthinker``: the global run, not the window run)
FORWARD_CALLS = {
    "glm4_moe_lite": ("seq_attention_fwd", 8, 4),    # dense + expert body
    "lfm2_moe": ("seq_attention_fwd", 4, 2),
    "qwen3_next": ("seq_attention_fwd", 4, 2),       # the full run
    "smallthinker": ("seq_attention_fwd", 6, 2),     # the window run's
    "sdar_moe": ("seq_attention_bd_fwd", 2, 0),
    "xing4_0": ("seq_attention_fwd", 8, 4),          # GLM's two bodies
}


@pytest.mark.parametrize("name", sorted(BACKBONES))
def test_which_turns_run_attentions_forward_twice(name):
    """The train step's jaxpr, backbone by backbone: ``glm4_moe_lite``,
    ``lfm2_moe`` and ``qwen3_next`` hold the forward kernel calls they
    held before any turn kept anything (their ``_stack`` carries no
    such policy), ``smallthinker`` one call site fewer, ``sdar_moe``
    half — and all of them as many dq and dk/dv calls as bodies."""
    module, c = BACKBONES[name]
    calls = kernel_calls(jax.make_jaxpr(_program(module, c))(
        *_abstract_args(module, c)).jaxpr)
    fwd, total, recomputed = FORWARD_CALLS[name]
    assert calls[fwd] == total
    bodies = (total - recomputed) // 2
    assert calls[fwd.replace("fwd", "dq")] == 2 * bodies
    assert calls[fwd.replace("fwd", "dkv")] == 2 * bodies
    assert len(calls) == 3
