"""What the block-stack backbones of the ``sequentialrec`` template
share, and the table that names them.

A backbone is a module of this package that trains a published block
on PACKED item histories (the item catalog in place of the token
vocabulary; id 0 is PAD) as one chip's share of an expert-parallel
job. Each keeps only what is its block's: its config, parameter
shapes, operators, stack and loss, and ends in ONE declaration of them
handed to :func:`build`. Everything else is here, once:

- the table: ``architecture["model_type"]`` → :class:`Backbone`
  (:func:`backbone`), what the config classes share
  (:class:`ArchitectureConfig`), and :func:`build`, which makes of a
  declaration every function the table hands out — the parameter
  count, the state and its init program, the gradient groups, the
  train program, the train verb, the logits and the next-item program
  — each compiled program kept ONCE per (frozen, hashable) config;
- :func:`pack_histories`: histories → ``seq_len``-slot sequences with
  segment ids, positions that restart with each segment, targets that
  never cross a segment's end; with a ``window``, the pairs it leaves;
  with a ``block``, the blocks and the pairs the block rule leaves;
  with a ``chunk``, the chunks a recurrence's scan walks and those a
  segment starts inside;
- the two MASKS a backbone may train under: causal inside a segment
  (:func:`attention`: a row sees its segment's keys up to itself,
  under a ``window`` its newest keys only), and the block rule of
  block diffusion (:func:`block_attention`: a clean and a noised copy
  of every sequence in one pass, a row's keys decided by BLOCKS of its
  segment) — and beside them a RECURRENCE: a layer that carries a
  state along the segment instead of reading keys
  (:mod:`predictionio_tpu.ops.gated_delta`, the backbone's own call:
  the state is zero at a segment's first row, as a mask lets no key of
  another segment through);
- the two OBJECTIVES: the next item (``_chunked_ce`` over shifted
  targets, every real row once) and the block-diffusion one — a
  noised row predicts its OWN item, weighted by 1/p of its block's
  masking rate (``_chunked_ce`` with ``weights``; :func:`block_noise`
  draws the masks, a pure function of seed, step, sequence and slot);
- the pieces of a block: ``_mm`` (operands in the matmul dtype,
  float32 accumulation), ``_rms``, ``_rope`` (the backbone applies it
  where its layer has positions: a layer without applies none; with
  the config's own frequencies where it scales them),
  :func:`attention` and :func:`block_attention`
  (:mod:`predictionio_tpu.ops.seq_attention`), ``_swiglu``, the
  expert layer in two halves — ``_route`` (ids, gates, plan and load
  from ONE tensor: sigmoid scores with a selection bias, or a softmax
  over the selected logits) and ``_experts`` (dispatch → grouped gated
  units → combine on ANOTHER, later; the unit's activation is the
  backbone's; a shared expert where the layer's weights hold one,
  behind its sigmoid gate where they hold that too) —
  and ``_moe``, both halves on the same rows
  (:mod:`predictionio_tpu.ops.moe_dispatch`), ``_cast_in_loop``,
  ``_chunked_ce``;
- :func:`scope`: the ``seqrec.*`` names the device trace is read by
  (:data:`SCOPES`), and :func:`program_name`, which puts them into the
  train program's identity;
- :func:`init_program` (one jitted program makes parameters, Adam's
  state and the router bias on the device), :func:`train_program` (the
  step: gradients, group norms, clipping, Adam, the router-bias rule;
  a scan over epochs of a scan over steps; ``counted``: the loss is
  also told how many steps were taken) and :func:`train_histories`
  (the verb's ``seqrec.pack`` / ``.init`` / ``.fit`` / ``.fetch`` spans
  with their counters, through ``seq_rec.run_epoch_blocks``; which
  counters beside the shared ones, which arrays beside ``Packed``'s,
  are the backbone's declaration, a window, a block length, a chunk
  and a MASK id its config's);
- :func:`next_item_scores`: one history, one segment, through the
  same stack — the item after it read at its last row, or (a backbone
  that fills blocks) at the first of the MASK rows appended to it.

Precision, for every backbone: float32 master parameters, gradients,
Adam moments and residual stream; matmul operands ``matmul_dtype``
(bfloat16) with float32 accumulation; router scores, softmax, the
norms' statistics and the loss in float32.
"""

from __future__ import annotations

import functools
import hashlib
import importlib
import math
from dataclasses import fields
from typing import (Any, Callable, ClassVar, Dict, List, NamedTuple,
                    Optional, Sequence, Tuple)

import numpy as np

from predictionio_tpu.ops import moe_dispatch, seq_attention

# -- the table ----------------------------------------------------------------


class Backbone(NamedTuple):
    """What the template and the benchmark ask of a backbone, as
    :func:`build` makes it of the backbone's declaration. Which
    layers have a window or rotary positions or carry a recurrent
    state in place of attention, where the router reads, what its
    experts' activation is, which mask it trains under (causal, or the
    block rule over two streams) and which objective
    (the next item, or the items a noised copy hides) are the
    backbone's own affair: it passes them to :func:`attention` or
    :func:`block_attention`, ``_route``, ``_experts`` and
    ``_chunked_ce``."""
    model_type: str
    config: type                   # .from_architecture(arch) -> config
    #: (histories, config, epochs, lr, seed, checkpoint_dir=,
    #: checkpoint_every=) -> ({"params", "bias"} on the host, the losses
    #: of the steps run): :func:`train_histories`
    train: Callable
    #: (model, batch, config) -> each head's logits [B, S, V] (a
    #: next-item head's at every row; a block-diffusion head's at the
    #: rows of the NOISED stream the batch carries)
    sequence_logits: Callable
    #: (model, history, config) -> scores over the vocabulary:
    #: :func:`next_item_scores`
    next_item_scores: Callable
    #: per head, the name of its loss in the step's record (``xing4_0``,
    #: whose config decides whether the MTP head exists: those it MAY
    #: train; its config's ``heads`` names those it does)
    heads: Tuple[str, ...]
    #: what ``sequence_logits`` reads of a packed batch (of ``Packed``;
    #: a block-diffusion backbone besides: ``noised``, ``weight``)
    batch_keys: Tuple[str, ...]
    init_state: Callable           # (config, seed, with_optimizer=)
    n_params: Callable             # (config) -> int
    group_squares: Callable        # (gradient tree) -> {group: Σ g²}
    # -- beside the ten names the benchmark reads: what the tests, the
    # -- verb and a later serving path reach --------------------------
    param_shapes: Callable         # (config) -> the parameter tree as shapes
    #: (params, bias, batch, config) -> (loss, the step's records)
    loss_fn: Callable
    #: (config) -> the groups, in the order ``group_norms`` records
    grad_groups: Callable
    #: (config, epochs) -> the compiled ``train(state, data)``
    train_program: Callable
    #: of ``Packed``, what a TRAIN's batches hold
    train_keys: Tuple[str, ...]
    #: (config) -> the compiled programs behind ``sequence_logits`` and
    #: ``next_item_scores``
    logits_program: Callable
    next_program: Callable
    #: the backbone's own part of the verb's spans: ``fit_attrs(config)``
    #: on ``seqrec.fit``; ``pack_attrs(packed, config)`` on
    #: ``seqrec.pack`` (it may refuse the histories); ``draws(packed,
    #: seed)`` the per-sequence arrays [sequences, …] a train's batches
    #: hold beside ``train_keys`` (what keys a backbone's noise). Each
    #: gives a dict, empty where a backbone has none.
    fit_attrs: Callable
    pack_attrs: Callable
    draws: Callable


#: ``model_type`` → the module that defines ``BACKBONE``
_MODULES = {"glm4_moe_lite": "predictionio_tpu.models.glm4_moe_lite",
            "lfm2_moe": "predictionio_tpu.models.lfm2_moe",
            "smallthinker": "predictionio_tpu.models.smallthinker",
            "sdar_moe": "predictionio_tpu.models.sdar_moe",
            "qwen3_next": "predictionio_tpu.models.qwen3_next",
            "xing4_0": "predictionio_tpu.models.xing4_0"}
#: an ``architecture`` without ``model_type``, and a model saved before
#: the table existed
DEFAULT = "glm4_moe_lite"


def backbone(model_type: Optional[str] = None) -> Backbone:
    model_type = model_type or DEFAULT
    if model_type not in _MODULES:
        raise ValueError(f"architecture.model_type = {model_type!r}: "
                         f"implemented are {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[model_type]).BACKBONE


class ArchitectureConfig:
    """What the backbones' config classes share: METHODS only. Every
    field is the frozen dataclass's own that derives from this — the
    config object is pickled, field by field, into a saved model's
    head. A class names ``_REQUIRED`` (what the published config may
    say and its file can honour), ``_UNUSED`` (published keys that size
    nothing there) and ``_HELD`` (its field for the routed experts HELD
    on this chip; the router is ``ep_size`` times as wide)."""
    _REQUIRED: ClassVar[Dict[str, Any]]
    _UNUSED: ClassVar[Tuple[str, ...]]
    _HELD: ClassVar[str]

    @classmethod
    def from_architecture(cls, arch: Dict[str, Any]):
        """The ``architecture`` object of the algorithm's parameters:
        the published config's keys (and the class's own). A key of
        ``_REQUIRED`` that says otherwise and a key nobody knows are
        refused; a list becomes a tuple (the config is hashed). A class
        adds its OWN checks after."""
        for key, want in cls._REQUIRED.items():
            if key in arch and arch[key] != want:
                raise ValueError(f"architecture.{key} = {arch[key]!r}: "
                                 f"only {want!r} is implemented")
        unknown = set(arch) - cls.known_keys()
        if unknown:
            raise ValueError(f"unknown architecture keys {sorted(unknown)}")
        names = {f.name for f in fields(cls)}
        return cls(**{k: tuple(v) if isinstance(v, list) else v
                      for k, v in arch.items() if k in names})

    @classmethod
    def known_keys(cls) -> frozenset:
        """Every key an ``architecture`` object may hold."""
        return frozenset({f.name for f in fields(cls)} | set(cls._REQUIRED)
                         | set(cls._UNUSED))

    @property
    def router_experts(self) -> int:
        return getattr(self, self._HELD) * self.ep_size

    @property
    def held(self) -> Tuple[int, ...]:
        n = getattr(self, self._HELD)
        return tuple(range(self.ep_rank * n, (self.ep_rank + 1) * n))

    # what :func:`train_histories` and :func:`next_item_scores` read of
    # ANY config; the backbone that has one overrides it
    @property
    def window(self) -> Optional[int]:
        """The key window of the window layers; None: no layer has one."""
        return None

    @property
    def block_length(self) -> Optional[int]:
        """Rows of a block of the block rule; None: causal training."""
        return None

    @property
    def chunk(self) -> Optional[int]:
        """Rows of a chunk of a recurrence's scan; None: no layer
        carries a state."""
        return None

    @property
    def mask_id(self) -> Optional[int]:
        """The MASK row of the vocabulary; None: it has none."""
        return None


def build(config: type, *, param_shapes: Callable, bias_shape: Callable,
          group_squares: Callable, loss_fn: Callable, logits: Callable,
          next_logits: Callable, heads: Tuple[str, ...],
          batch_keys: Tuple[str, ...],
          train_keys: Optional[Tuple[str, ...]] = None,
          counted: bool = False, fit_attrs: Optional[Callable] = None,
          pack_attrs: Optional[Callable] = None,
          draws: Optional[Callable] = None,
          init_leaf: Optional[Callable] = None) -> Backbone:
    """A backbone's DECLARATION → the :class:`Backbone` the table hands
    out. The declaration is what is the backbone's own: its ``config``
    class (an :class:`ArchitectureConfig`), ``param_shapes(c)``,
    ``bias_shape(c)`` (layers with a router, ``c.router_experts``),
    ``group_squares(grads)``, ``loss_fn(params, bias, batch, c)`` and
    whether it is ``counted`` (:func:`train_program`), ``logits(params,
    bias, batch, c)`` → a tuple, one array [B, S, V] a head of ``heads``,
    ``next_logits(params, bias, batch, n, c)`` → [V] of the item after
    the ``n`` tokens of a one-segment batch, ``batch_keys`` (and
    ``train_keys`` where a train's batches hold others), and its part of
    the verb's spans (``fit_attrs``, ``pack_attrs``, ``draws``:
    :class:`Backbone`), and ``init_leaf`` where some leaf starts as
    neither a normal matrix nor a unit gain (:func:`init_program`).
    Everything a caller runs is made HERE, the same
    for every backbone, and each compiled program is kept once per
    config — the configs are frozen dataclasses, hashed by value."""
    def n_params(c) -> int:
        return count_params(param_shapes(c))

    @functools.lru_cache(maxsize=4)
    def init_compiled(c, with_optimizer: bool):
        return init_program(c, param_shapes(c), bias_shape(c),
                            with_optimizer, init_leaf)

    def init_state(c, seed: int, with_optimizer: bool = False):
        """(params, router bias) made ON the device from the seed, by
        one jitted program (:func:`init_program`); ``with_optimizer``:
        Adam's zeroed state too — (params, opt_state, bias)."""
        return init_compiled(c, with_optimizer)(np.uint32(seed % (1 << 32)))

    @functools.lru_cache(maxsize=8)
    def groups_of(c) -> Tuple[str, ...]:
        return grad_groups(group_squares, param_shapes(c))

    @functools.lru_cache(maxsize=8)
    def train_compiled(c, epochs: int):
        return train_program(c, epochs, loss_fn, group_squares,
                             groups_of(c), counted)

    def train(histories, c, epochs: int, lr: float, seed: int,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 1) -> Tuple[Dict, np.ndarray]:
        return train_histories(built, histories, c, epochs, lr, seed,
                               checkpoint_dir, checkpoint_every)

    @functools.lru_cache(maxsize=16)
    def logits_compiled(c):
        # named after the DECLARED function (a lambda: ``jit__lambda``):
        # the name is part of a program's key in the persistent
        # compilation cache (:func:`program_name`), so renaming one
        # compiles it once more on every machine that had it
        import jax

        def program(params, bias, batch):
            return logits(params, bias, batch, c)

        program.__name__ = logits.__name__
        return jax.jit(program)

    def sequence_logits(model: Dict, batch: Dict[str, np.ndarray], c):
        """Each head's float32 logits of whole packed sequences (a
        tuple, one array a head), by the program."""
        return logits_compiled(c)(model["params"], model["bias"], batch)

    @functools.lru_cache(maxsize=16)
    def next_compiled(c):
        return next_program(lambda params, bias, batch, n: next_logits(
            params, bias, batch, n, c))

    def scores(model: Dict, history: Sequence[int], c) -> np.ndarray:
        return next_item_scores(built, model, history, c)

    built = Backbone(
        model_type=config.model_type, config=config, train=train,
        sequence_logits=sequence_logits, next_item_scores=scores,
        heads=heads, batch_keys=batch_keys, init_state=init_state,
        n_params=n_params, group_squares=group_squares,
        param_shapes=param_shapes, loss_fn=loss_fn, grad_groups=groups_of,
        train_program=train_compiled, train_keys=train_keys or batch_keys,
        logits_program=logits_compiled, next_program=next_compiled,
        fit_attrs=fit_attrs or (lambda c: {}),
        pack_attrs=pack_attrs or (lambda packed, c: {}),
        draws=draws or (lambda packed, seed: {}))
    return built


# -- packing ------------------------------------------------------------------


class Packed(NamedTuple):
    tokens: np.ndarray    # [N, S] int32 item ids, 0 = PAD
    seg: np.ndarray       # [N, S] int32 segment of the slot, 0 = PAD
    pos: np.ndarray       # [N, S] int32 position inside the segment
    tgt1: np.ndarray      # [N, S] int32 next item of the segment, 0 = none
    tgt2: np.ndarray      # [N, S] int32 the item after it, 0 = none
    counters: Dict[str, int]


def _targets(tokens: np.ndarray, seg: np.ndarray, ahead: int) -> np.ndarray:
    out = np.zeros_like(tokens)
    same = (seg[:, ahead:] == seg[:, :-ahead]) & (seg[:, :-ahead] > 0)
    out[:, :-ahead] = np.where(same, tokens[:, ahead:], 0)
    return out


def window_pairs(sizes: np.ndarray, window: int) -> Tuple[int, int]:
    """Of segments of ``sizes`` rows under a window of ``window`` keys:
    the (query, key) pairs causal attention still sees — row i of a
    segment its newest min(i + 1, window) keys — and the rows whose
    window cuts keys off (those ``window`` or more behind their
    segment's start)."""
    sizes = np.asarray(sizes, np.int64)
    short = np.minimum(sizes, window)
    over = sizes - short
    return (int((short * (short + 1) // 2 + over * window).sum()),
            int(over.sum()))


def pack_histories(histories: Sequence[Sequence[int]], seq_len: int,
                   seqs_per_step: int = 1, seed: int = 0,
                   window: Optional[int] = None,
                   block: Optional[int] = None,
                   chunk: Optional[int] = None) -> Packed:
    """Histories (item ids ≥ 1, oldest first) → ``seq_len``-slot
    sequences with segment ids. A history longer than a sequence is
    cut into ``seq_len`` pieces; pieces go whole, longest first, into
    the first of ⌈tokens / seq_len⌉ sequences with room, and one that
    fits nowhere whole is cut to fill the gaps. Each piece is a
    segment: attention, a short convolution's taps, RoPE positions and
    targets stay inside it. The sequence count is padded to a multiple
    of ``seqs_per_step`` and the order shuffled by ``seed``. With a
    ``window`` the counters also hold what it leaves of the segments'
    pairs (``attn_pairs_window``) and the rows it binds
    (``window_bound_tokens``), by :func:`window_pairs`; with a
    ``block``, the blocks a segment is cut into from its first row
    (``bd_blocks``; ``bd_partial_blocks`` of them shorter than
    ``block``), the pairs the block rule leaves of both streams
    (``attn_pairs_bd``, by :func:`seq_attention.block_pairs`) and the
    rows the two streams make (``stream_rows``); with a ``chunk``, the
    chunks of ``chunk`` rows a recurrence's scan walks over every
    sequence (``gdn_chunks``) and those that hold a segment's first row
    after their own (``gdn_boundary_chunks``: the state is reset INSIDE
    them)."""
    S = int(seq_len)
    pieces: List[np.ndarray] = []
    n_hist = n_split = 0
    for h in histories:
        h = np.asarray(h, np.int32)
        h = h[h > 0]
        if h.size < 2:
            continue
        n_hist += 1
        n_split += h.size > S
        pieces += [h[a:a + S] for a in range(0, h.size, S)]
    if not pieces:
        raise ValueError("no trainable history (all shorter than 2)")
    total = sum(p.size for p in pieces)
    n_seq = -(-total // S)
    room = np.full(n_seq, S, np.int64)
    bins: List[List[np.ndarray]] = [[] for _ in range(n_seq)]
    left: List[np.ndarray] = []
    for p in sorted(pieces, key=lambda p: -p.size):
        fit = np.flatnonzero(room >= p.size)
        if fit.size:
            bins[fit[0]].append(p)
            room[fit[0]] -= p.size
        else:
            left.append(p)
    for p in left:
        n_split += 1
        while p.size:
            b = int(np.argmax(room > 0))
            take = int(min(room[b], p.size))
            bins[b].append(p[:take])
            room[b] -= take
            p = p[take:]
    n_all = -(-n_seq // seqs_per_step) * seqs_per_step
    tokens = np.zeros((n_all, S), np.int32)
    seg = np.zeros((n_all, S), np.int32)
    pos = np.zeros((n_all, S), np.int32)
    for b, rows in enumerate(bins):
        at = 0
        for j, p in enumerate(rows):
            tokens[b, at:at + p.size] = p
            seg[b, at:at + p.size] = j + 1
            pos[b, at:at + p.size] = np.arange(p.size)
            at += p.size
    order = np.random.default_rng(seed).permutation(n_all)
    tokens, seg, pos = tokens[order], seg[order], pos[order]
    tgt1, tgt2 = _targets(tokens, seg, 1), _targets(tokens, seg, 2)
    sizes = np.asarray([p.size for rows in bins for p in rows], np.int64)
    counters = {
        "histories": n_hist, "split": int(n_split), "sequences": n_all,
        "slots": n_all * S, "real_tokens": int(total),
        # (query, key) pairs causal attention inside the segments sees
        "attn_pairs": int((sizes * (sizes + 1) // 2).sum()),
        "targets": int((tgt1 > 0).sum()),
        "mtp_targets": int((tgt2 > 0).sum())}
    if window is not None:
        (counters["attn_pairs_window"],
         counters["window_bound_tokens"]) = window_pairs(sizes, window)
    if block is not None:
        counters.update(
            bd_block=int(block), bd_blocks=int((-(-sizes // block)).sum()),
            bd_partial_blocks=int((sizes % block > 0).sum()),
            attn_pairs_bd=seq_attention.block_pairs(sizes, block),
            stream_rows=2 * n_all * S)
    if chunk is not None:
        first = np.zeros((n_all, S), bool)
        first[:, 1:] = (seg[:, 1:] != seg[:, :-1]) & (seg[:, 1:] > 0)
        first[:, ::chunk] = False   # at a chunk's first row: nothing inside
        inside = np.add.reduceat(first, np.arange(0, S, chunk), axis=1) > 0
        counters.update(gdn_chunk=int(chunk), gdn_chunks=int(inside.size),
                        gdn_boundary_chunks=int(inside.sum()))
    return Packed(tokens, seg, pos, tgt1, tgt2, counters)


# -- parameter trees ----------------------------------------------------------


def _swiglu_shapes(d: int, f: int, lead: tuple = ()) -> Dict[str, tuple]:
    return {"wg": lead + (d, f), "wu": lead + (d, f), "wd": lead + (f, d)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _stacked(tree, n: int):
    """A layer's shapes with a leading layer axis: identical layers
    are ONE scanned body."""
    import jax

    return jax.tree.map(lambda s: (n,) + s, tree, is_leaf=_is_shape)


def _path_name(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def count_params(shapes) -> int:
    import jax

    return sum(int(np.prod(s)) for s in
               jax.tree.leaves(shapes, is_leaf=_is_shape))


def squares_by_group(grads, group_of: Callable[[str], str]) -> Dict[str, Any]:
    """Σ g² per parameter group of a gradient tree; ``group_of`` names
    a leaf's group from its dotted path."""
    import jax
    import jax.numpy as jnp

    out: Dict[str, Any] = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        group = group_of(_path_name(path))
        out[group] = out.get(group, 0.0) + jnp.sum(
            jnp.square(g.astype(jnp.float32)))
    return out


def init_program(c, shapes, bias_shape: tuple, with_optimizer: bool,
                 init_leaf: Optional[Callable] = None):
    """``init(seed) -> (params, [Adam's zeroed state,] router bias)``,
    made ON the device by one jitted program: normal(0, init_std)
    matrices, unit gains for every leaf named ``*norm``, zero bias, the
    PAD row of the embedding zero. ``init_leaf(name, key, shape)``: a
    backbone's own start of a leaf (its dotted path), or None for the
    rule here."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.seq_rec import _make_tx

    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)

    def init(seed):
        # the hardware generator: hundreds of millions of normals
        # through threefry would cost more to compile than to draw
        keys = jax.random.split(jax.random.key(seed, impl="rbg"),
                                len(leaves))
        out = []
        for key, (path, shape) in zip(keys, leaves):
            own = init_leaf and init_leaf(_path_name(path), key, shape)
            if own is not None:
                out.append(own)
            elif _path_name(path).endswith("norm"):
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(c.init_std * jax.random.normal(
                    key, shape, jnp.float32))
        params = jax.tree_util.tree_unflatten(treedef, out)
        params["embed"] = params["embed"].at[0].set(0.0)
        bias = jnp.zeros(bias_shape, jnp.float32)
        if with_optimizer:
            return params, _make_tx().init(params), bias
        return params, bias

    return jax.jit(init)


# -- the scopes ---------------------------------------------------------------

#: Every ``jax.named_scope`` a backbone's train program opens: the
#: device trace is read by them (``benchmark/scope_reduce.py`` gives an
#: operation to the INNERMOST one on its path, so a scope goes around
#: or beside another, never into one whose seconds a metric reads).
#: ``moe_dispatch`` opens its three itself.
SCOPES = frozenset({
    "seqrec.step",          # a step's body; self: what no other names
    "seqrec.embed", "seqrec.head", "seqrec.optimizer",
    "seqrec.stack",         # a scan over a run of layers; self: the
                            # scan's own slices, stacking and carries
    "seqrec.stack.cast",    # _cast_in_loop
    "seqrec.norm",          # the norm between operator and FFN / experts,
                            # and the experts' copy in the matmul dtype
    "seqrec.residual",      # the expert branch's residual add (xing4_0:
                            # the write-back, under seqrec.mhc.mix)
    "seqrec.ffn", "seqrec.moe.route", "seqrec.moe.dispatch",
    "seqrec.moe.experts", "seqrec.moe.combine",
    "seqrec.mla", "seqrec.mla.attention", "seqrec.mtp",   # glm4_moe_lite
    "seqrec.conv", "seqrec.conv.mix",                     # lfm2_moe
    "seqrec.gqa", "seqrec.gqa.attention",   # lfm2_moe; smallthinker: global
    "seqrec.swa", "seqrec.swa.attention",   # smallthinker: window layers
    "seqrec.bd", "seqrec.bd.attention", "seqrec.bd.noise",      # sdar_moe
    # qwen3_next: the linear layers (the full one: seqrec.gqa*) — the
    # projections, gates and gated norm; the convolution; the scan
    "seqrec.gdn", "seqrec.gdn.conv", "seqrec.gdn.scan",
    # xing4_0 (its attention: seqrec.mla*): the n-copy residual stream —
    # self: the copies and the fold; the coefficients (norm, projection,
    # sigmoids, Sinkhorn); the read u and the write-back
    "seqrec.mhc", "seqrec.mhc.coef", "seqrec.mhc.mix"})


def scope(name: str):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`; any
    other is refused, because :func:`program_name` would not know it."""
    import jax

    if name not in SCOPES:
        raise ValueError(f"scope {name!r} is not in seq_backbone.SCOPES")
    return jax.named_scope(name)


def program_name() -> str:
    """The train function's name, with a digest of :data:`SCOPES`.

    A scope lives only in an operation's debug info, and JAX keys its
    persistent compilation cache on the module AFTER stripping that: a
    program that differs from a cached one in its scopes alone is
    answered with the cached executable, and the trace shows the old
    names. The function's name (the module's ``sym_name``) is hashed,
    so a program whose scope set changed is compiled once more and hit
    ever after. Not ``jax_compilation_cache_include_metadata_in_key``:
    that puts every traced frame's file path into the key, and a
    checkout unpacked elsewhere would never hit."""
    digest = hashlib.sha256(" ".join(sorted(SCOPES)).encode("ascii"))
    return f"train_{digest.hexdigest()[:8]}"


# -- the pieces of a block ----------------------------------------------------


def _attn_tiles(c, S: int) -> Tuple[int, int]:
    """(query rows, keys) of an attention tile on ``S`` slots: the
    most that ``attn_block`` and the kernels' key tile allow and that
    divide S."""
    return math.gcd(c.attn_block, S), math.gcd(seq_attention.KEY_TILE, S)


def _dt(c):
    import jax.numpy as jnp

    return jnp.dtype(c.matmul_dtype)


def _mm(x, w, c):
    """Operands in the matmul dtype, float32 accumulation and result."""
    import jax.numpy as jnp

    return jnp.dot(x.astype(_dt(c)), w.astype(_dt(c)),
                   preferred_element_type=jnp.float32)


def _rms(x, g, eps: float):
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, pos, theta: float, freqs: Optional[Sequence[float]] = None):
    """Rotate-half RoPE over the last axis, in float32; ``pos``
    broadcasts against x's leading axes. ``freqs``: the half's
    frequencies where the config scales them (YaRN), else
    θ^(−i/half)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    if freqs is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freq = jnp.asarray(freqs, jnp.float32)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(q, k, v, seg, c, scale: float, window: Optional[int] = None):
    """Causal, segment-masked attention of ONE sequence: q [S, H, D],
    k [S, Hkv, D], v [S, Hkv, Dv] (query head h reads key-value head
    h ÷ (H ÷ Hkv)) → [S, H, Dv] in v's dtype. Tiles of at most
    ``attn_block`` query rows, and only those between a block's
    earliest key — of its earliest segment, or ``window`` − 1 rows
    before the block where that lies later — and the diagonal
    (:mod:`predictionio_tpu.ops.seq_attention`)."""
    return seq_attention.segment_attention(
        q, k, v, seg, *_attn_tiles(c, q.shape[0]), scale, window)


def block_attention(q, k, v, seg, c, scale: float, block: int):
    """Attention of BOTH streams of one sequence under the block rule:
    q [2·S, H, D], k [2·S, Hkv, D], v [2·S, Hkv, Dv] — the clean rows,
    then the noised ones — and ``seg`` [S] → [2·S, H, Dv]. A clean row
    sees its segment's clean keys up to the end of its block of
    ``block`` rows, a noised row the clean keys before its block and
    its block's noised keys; the tiles are those :func:`attention`
    takes on S slots, and only those the rule reaches are visited
    (:func:`predictionio_tpu.ops.seq_attention.block_attention`)."""
    return seq_attention.block_attention(
        q, k, v, seg, *_attn_tiles(c, seg.shape[0]), scale, block)


def block_noise(seed, step, sequence, seg, block: int, eps: float):
    """One sequence's masks for one step of block-diffusion training:
    (``masked`` [S] bool, ``weight`` [S] float32). Each block of
    ``block`` rows of a segment draws p = ε + (1 − ε)·u, u ~ U(0, 1);
    each of its rows is masked with probability p and then weighs 1/p
    in the loss, any other row 0; a padding row is never masked. A
    pure function of (``seed``, ``step`` — the steps taken before this
    one, over all epochs —, ``sequence`` — its number in the packed
    order —, slot): threefry, so the same bits on any device, and a
    fresh draw every epoch."""
    import jax
    import jax.numpy as jnp

    S = seg.shape[-1]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed, impl="threefry2x32"), step), sequence)
    rate, coin = (jax.random.uniform(k, (S,), jnp.float32)
                  for k in jax.random.split(key))
    # a block's draw is the one of its first row's slot
    _, start, _ = seq_attention.block_spans(seg, block)
    # the maximum changes nothing (the product is never negative) and
    # keeps a compiler from fusing product and sum into one rounding:
    # the same bits from every backend, jitted or not
    p = eps + jnp.maximum((1.0 - eps) * rate[jnp.minimum(start, S - 1)], 0.0)
    masked = (coin < p) & (seg > 0)
    return masked, jnp.where(masked, 1.0 / p, 0.0)


def _swiglu(w, x, c):
    """W_d(silu(W_g x) ⊙ W_u x), ``token_chunk`` tokens at a time; the
    wide intermediates are recomputed in the backward pass, never
    kept for all the tokens at once."""
    import jax

    @jax.checkpoint
    def chunk(x):
        return _mm(jax.nn.silu(_mm(x, w["wg"], c)) * _mm(x, w["wu"], c),
                   w["wd"], c)

    d = x.shape[-1]
    rows = x.reshape(-1, d)
    n = min(c.token_chunk, rows.shape[0])
    if rows.shape[0] % n:
        raise ValueError(f"{rows.shape[0]} tokens are no multiple of "
                         f"token_chunk {n}")
    return jax.lax.map(chunk, rows.reshape(-1, n, d)).reshape(x.shape)


def _route(router, x, valid, bias, c, softmax: bool = False):
    """The expert layer's ROUTE from x [T, d] float32, whatever rows
    the backbone routes on: (gates [T, k] float32, the dispatch plan,
    what the step records of the routing). Scores sigmoid(x W_r) with
    the selection-only ``bias``, gates scaling · s_e/Σ_selected s — or
    (``softmax``) a softmax over the selected logits, no bias and no
    scaling. The router's product and its activation are float32."""
    import jax
    import jax.numpy as jnp

    E, k = c.router_experts, c.num_experts_per_tok
    with scope("seqrec.moe.route"):
        logits = jnp.dot(x, router, precision=jax.lax.Precision.HIGHEST)
        if softmax:
            ids, gates = moe_dispatch.route_softmax(logits, k)
        else:
            ids, gates = moe_dispatch.route(
                jax.nn.sigmoid(logits), bias, k, c.routed_scaling_factor,
                c.norm_topk_prob)
        p = moe_dispatch.plan(ids, c.held, E, valid)
        load = jnp.zeros(E, jnp.float32).at[ids.reshape(-1)].add(
            jnp.repeat(valid, k).astype(jnp.float32))
    held = load[jnp.asarray(c.held)]
    return gates, p, {
        "load": load, "pairs": valid.sum() * k, "pairs_here": p.pairs_here,
        "dropped": p.pairs_here - p.rows,
        "blocks_run": moe_dispatch.blocks_run(p),
        "blocks": jnp.int32(moe_dispatch.row_blocks(p.order.shape[0])[0]),
        "load_max_over_mean": held.max() / jnp.maximum(held.mean(), 1e-9)}


def _experts(w, x, gates, p, c, act=None):
    """x [T, d] float32 (normed) through the route ``gates``, ``p`` of
    :func:`_route`: this chip's part of the layer's result [T, d] —
    dispatch, the held experts' gated units (``act``: SiLU where none
    is given), combine. The shared expert is added where the layer has
    one (``w["shared"]``), times σ(x · w_s) where it has a gate
    (``w["shared_gate"]`` [d]; the gate's product float32)."""
    import jax
    import jax.numpy as jnp

    with scope("seqrec.norm"):      # the normed rows as the products take them
        rows = x.astype(_dt(c))
    out = moe_dispatch.experts_swiglu(
        rows, w["experts"]["wg"].astype(_dt(c)),
        w["experts"]["wu"].astype(_dt(c)), w["experts"]["wd"].astype(_dt(c)),
        gates, p, act)
    if "shared" in w:
        with scope("seqrec.ffn"):
            shared = _swiglu(w["shared"], x, c)
            if "shared_gate" in w:
                shared = shared * jax.nn.sigmoid(jnp.dot(
                    x, w["shared_gate"],
                    precision=jax.lax.Precision.HIGHEST))[:, None]
            out = out + shared
    return out


def _moe(w, x, valid, bias, c):
    """x [T, d] float32 (normed) → this chip's part of the layer's
    result [T, d], and what the step records of the routing: route and
    experts on the SAME rows (sigmoid router, SiLU experts)."""
    gates, p, stats = _route(w["router"], x, valid, bias, c)
    return _experts(w, x, gates, p, c), stats


def _cast_in_loop(w, c, turn, aside: Tuple[str, ...] = ("router",)):
    """A scanned layer's matrices in the matmul dtype, those named in
    ``aside`` left alone (the router: its product is float32), cast
    INSIDE the loop. Left to itself the compiler hoists the casts out
    of the loop — a bfloat16 copy of ALL the layers' weights, 0.7 GB at
    the GLM cell's size, for the whole step — and no
    ``optimization_barrier`` stops it; a factor of one that is computed
    from the loop's counter ``turn`` does, in the same fused pass as
    the cast."""
    import jax.numpy as jnp

    def cast(w):
        return {k: (v if k in aside else cast(v) if isinstance(v, dict)
                    else (v * one).astype(_dt(c)) if v.ndim >= 2 else v)
                for k, v in w.items()}

    with scope("seqrec.stack.cast"):
        one = jnp.where(turn >= 0, 1.0, 0.0).astype(jnp.float32)
        return cast(w)


def _chunked_ce(logits_of, x, targets, c, weights=None):
    """Σ cross-entropy over the real targets — with ``weights`` (float32,
    like ``targets``), Σ weight · cross-entropy over every row;
    ``logits_of`` (rows [n, d] → float32 logits [n, V]) is applied
    ``token_chunk`` tokens at a time and its logits never kept."""
    import jax
    import jax.numpy as jnp

    d = x.shape[-1]
    x, t = x.reshape(-1, d), targets.reshape(-1)
    n = min(c.token_chunk, x.shape[0])

    @jax.checkpoint
    def chunk(xt):
        x, t, *w = xt
        logits = logits_of(x)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        if w:
            return (w[0] * (lse - hit)).sum()
        return jnp.where(t > 0, lse - hit, 0.0).sum()

    chunks = (x.reshape(-1, n, d), t.reshape(-1, n))
    if weights is not None:
        chunks += (weights.reshape(-1, n),)
    with scope("seqrec.head"):
        return jax.lax.map(chunk, chunks).sum()


# -- the train program --------------------------------------------------------


def grad_groups(group_squares, shapes) -> Tuple[str, ...]:
    """The parameter groups, in the order ``group_norms`` records."""
    import jax
    import jax.numpy as jnp

    return tuple(sorted(jax.eval_shape(group_squares, jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), shapes,
        is_leaf=_is_shape))))


def train_program(c, epochs: int, loss_fn, group_squares,
                  groups: Tuple[str, ...], counted: bool = False):
    """``train(state, data) -> (state, records)``: ``epochs`` passes
    over ``data`` ([steps, B, S] per key) as ONE compiled program, a
    scan over epochs of a scan over steps. ``state``: params, opt_state
    (:func:`predictionio_tpu.models.seq_rec._make_tx`), bias; the
    learning rate rides in the optimizer state. ``loss_fn(params, bias,
    batch, c) -> (loss, {a loss per head, "moe": the expert layers'
    routing records [layers, …], any further number of the step})``.
    ``counted``: the batch also carries ``step``, the steps taken
    before this one over all epochs (the optimizer's count, which a
    checkpoint restores) — what an objective that draws noise folds
    into its key, so that a resumed run draws what the whole one
    would have."""
    import jax
    import jax.numpy as jnp

    import optax

    from predictionio_tpu.models.seq_rec import _make_tx

    tx = _make_tx()

    @scope("seqrec.step")
    def step(state, batch):
        params, opt_state, bias = state
        if counted:
            batch = dict(batch, step=opt_state.count)
        (_, rec), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, bias, batch, c)
        moe = rec.pop("moe")
        with scope("seqrec.optimizer"):
            squares = group_squares(grads)
            norm = jnp.sqrt(sum(squares.values()))
            scale = jnp.minimum(1.0, c.clip_norm / (norm + 1e-6))
            grads = jax.tree.map(lambda g: g * scale, grads)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            load = moe["load"]
            bias = bias + c.bias_update_rate * jnp.sign(
                load.mean(axis=-1, keepdims=True) - load)
        record = dict(
            rec, grad_norm=norm,
            group_norms=jnp.stack([jnp.sqrt(squares[g]) for g in groups]),
            moe_pairs=moe["pairs"].sum(),
            moe_pairs_here=moe["pairs_here"].sum(),
            moe_dropped_pairs=moe["dropped"].sum(),
            moe_blocks_run=moe["blocks_run"].sum(),
            moe_blocks=moe["blocks"].sum(),
            moe_load_max_over_mean=moe["load_max_over_mean"].max(),
            router_bias_absmax=jnp.abs(bias).max())
        return (params, opt_state, bias), record

    def train(state, data):
        def epoch(state, _):
            return jax.lax.scan(step, state, data)

        if epochs == 1:
            return epoch(state, None)
        state, records = jax.lax.scan(epoch, state, None, length=epochs)
        return state, jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), records)

    train.__name__ = program_name()
    return jax.jit(train, donate_argnums=(0,))


def train_histories(backbone: Backbone, histories: Sequence[Sequence[int]],
                    c, epochs: int, lr: float, seed: int,
                    checkpoint_dir: Optional[str] = None,
                    checkpoint_every: int = 1) -> Tuple[Dict, np.ndarray]:
    """Train ``backbone`` on per-user item-id histories; returns the
    model's arrays on the HOST (``{"params", "bias"}``) and the loss of
    every step run in this process. Spans ``seqrec.pack`` / ``.init`` /
    ``.fit`` / ``.fetch`` land in the verb record
    (docs/observability.md). ``backbone.train_program(c, n)`` is the
    compiled train of ``n`` epochs; ``backbone.pack_attrs(packed, c)``
    and ``backbone.fit_attrs(c)`` add the backbone's own counters to
    the two spans; ``c.window``: the key window of the backbone's
    window layers, counted on ``seqrec.pack`` (``attn_pairs_window``,
    ``window_bound_tokens``, ``attn_tile_pairs_window``);
    ``c.block_length``: the block length of a backbone that trains
    under the block rule, counted there too (``bd_*``,
    ``attn_pairs_bd``, ``attn_tile_pairs_bd``, ``stream_rows``);
    ``c.chunk``: the chunk of a backbone whose layers carry a recurrent
    state (``gdn_chunk``, ``gdn_chunks``, ``gdn_boundary_chunks``);
    ``backbone.draws(packed, seed)``: further per-sequence arrays
    [sequences, …] of the batches (what the backbone's noise is keyed
    by). Every ``bd_*`` number of the steps' records is summed onto
    ``seqrec.fit``; ``mhc_ds_err`` (a backbone whose residual stream is
    mixed by a Sinkhorn-made matrix: how far from doubly stochastic) is
    the steps' largest."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.seq_rec import run_epoch_blocks
    from predictionio_tpu.utils import tracing

    if c.seq_len % min(c.attn_block, c.seq_len):
        raise ValueError("seq_len must be a multiple of attn_block")
    n_params, groups = backbone.n_params(c), backbone.grad_groups(c)
    window, block = c.window, c.block_length
    with tracing.span("seqrec.pack") as sp:
        packed = pack_histories(histories, c.seq_len, c.seqs_per_step, seed,
                                window, block, c.chunk)
        top = max(int(packed.tokens.max()), 0)
        if top >= c.vocab_size:
            raise ValueError(f"item id {top} outside the vocabulary of "
                             f"{c.vocab_size} rows")
        for k, v in packed.counters.items():
            sp.set_attr(k, v)
        # pairs inside the tiles attention visits (one epoch and head),
        # and inside those that blocks of ``attn_block`` rows walking
        # to the diagonal would
        bq, bk = _attn_tiles(c, c.seq_len)
        sp.set_attr("attn_tile_pairs",
                    seq_attention.tile_pairs(packed.seg, bq, bk))
        sp.set_attr("attn_dense_pairs",
                    seq_attention.tile_pairs(packed.seg, bq, bq, skip=False))
        if window is not None:
            sp.set_attr("attn_tile_pairs_window", seq_attention.tile_pairs(
                packed.seg, bq, bk, window=window))
        if block is not None:
            sp.set_attr("attn_tile_pairs_bd", seq_attention.block_tile_pairs(
                packed.seg, bq, bk, block))
        for k, v in backbone.pack_attrs(packed, c).items():
            sp.set_attr(k, v)
    with tracing.span("seqrec.init") as sp:
        B = c.seqs_per_step
        data = {k: jnp.asarray(getattr(packed, k).reshape(
            -1, B, packed.tokens.shape[1])) for k in backbone.train_keys}
        for k, v in backbone.draws(packed, seed).items():
            data[k] = jnp.asarray(v.reshape((-1, B) + v.shape[1:]))
        params, opt_state, bias = backbone.init_state(
            c, seed, with_optimizer=True)
        opt_state.hyperparams["learning_rate"] = jnp.float32(lr)
        state = jax.block_until_ready(
            {"params": params, "opt_state": opt_state, "bias": bias})
        sp.set_attr("params", n_params)
        sp.set_attr("bytes", 16 * n_params)
    steps = packed.tokens.shape[0] // c.seqs_per_step
    with tracing.span("seqrec.fit", steps=steps * epochs,
                      tokens_per_step=c.seqs_per_step * c.seq_len,
                      backbone=backbone.model_type,
                      **backbone.fit_attrs(c)) as sp:
        def run_block(state, n):
            out, rec = backbone.train_program(c, int(n))(
                (state["params"], state["opt_state"], state["bias"]), data)
            return (dict(zip(("params", "opt_state", "bias"), out)),
                    jax.device_get(rec))

        def set_lr(state):
            state["opt_state"].hyperparams["learning_rate"] = jnp.float32(lr)

        state, records = run_epoch_blocks(
            epochs, checkpoint_dir, checkpoint_every, state, run_block,
            set_lr)
        rec = ({k: np.concatenate([r[k] for r in records])
                for k in records[0]} if records else {})
        if rec:
            losses = [k for k in rec if k.endswith("loss")]
            for k in losses:
                sp.set_attr(f"{k}_first", float(rec[k][0]))
            sp.set_attr("loss_last4", float(rec["loss"][-4:].mean()))
            sp.set_attr("losses_finite", bool(all(
                np.isfinite(rec[k]).all() for k in losses)))
            sp.set_attr("grad_norms_first", {
                g: float(v) for g, v in zip(groups, rec["group_norms"][0])})
            for k in ("moe_pairs", "moe_pairs_here", "moe_dropped_pairs",
                      "moe_blocks_run", "moe_blocks",
                      *(k for k in rec if k.startswith("bd_"))):
                sp.set_attr(k, int(rec[k].sum()))
            sp.set_attr("moe_load_max_over_mean",
                        float(rec["moe_load_max_over_mean"].mean()))
            sp.set_attr("router_bias_absmax",
                        float(rec["router_bias_absmax"][-1]))
            if "mhc_ds_err" in rec:     # a backbone with an n-copy stream
                sp.set_attr("mhc_ds_err", float(rec["mhc_ds_err"].max()))
    with tracing.span("seqrec.fetch") as sp:
        host = jax.device_get({"params": state["params"],
                               "bias": state["bias"]})
        sp.set_attr("bytes", sum(a.nbytes for a in jax.tree.leaves(host)))
    del state
    return host, (rec["loss"] if rec else np.zeros(0, np.float32))


# -- serving ------------------------------------------------------------------


def next_program(last_logits):
    """``score(params, bias, tokens [S], n) -> logits [V]`` of the item
    after the first ``n`` tokens: ONE segment through the backbone's
    stack; ``last_logits(params, bias, batch, n)`` is its part."""
    import jax
    import jax.numpy as jnp

    def score(params, bias, tokens, n):
        S = tokens.shape[0]
        batch = {"tokens": tokens[None],
                 "seg": (jnp.arange(S) < n).astype(jnp.int32)[None],
                 "pos": jnp.arange(S, dtype=jnp.int32)[None]}
        return last_logits(params, bias, batch, n)

    return jax.jit(score)


def next_item_scores(backbone: Backbone, model: Dict,
                     history: Sequence[int], c) -> np.ndarray:
    """Scores over the vocabulary for the item after ``history`` (its
    last ``seq_len`` items, right-padded to a power-of-two bucket so
    that a handful of programs serve every length), by
    ``backbone.next_program(c)``; PAD = -inf. A config with a
    ``mask_id`` (a backbone that fills blocks of ``block_length``
    items): the newest ``seq_len − block_length`` items with MASK rows
    appended up to the end of the block after them — the program reads
    the FIRST of those rows —, and MASK = -inf too."""
    mask_id, block = c.mask_id, c.block_length
    seq = [i for i in history if i > 0][-c.seq_len:]
    if mask_id is not None:
        seq = [i for i in seq if i != mask_id][-(c.seq_len - block):]
        seq += [mask_id] * (block - len(seq) % block)
    bucket = min(c.seq_len, max(16, 1 << max(len(seq) - 1, 0).bit_length()))
    tokens = np.zeros(bucket, np.int32)
    tokens[:len(seq)] = seq
    logits = np.array(backbone.next_program(c)(
        model["params"], model["bias"], tokens, np.int32(max(len(seq), 1))))
    logits[0] = -np.inf
    if mask_id is not None:
        logits[mask_id] = -np.inf
    return logits
