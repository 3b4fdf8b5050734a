"""Self-attentive sequential recommendation (SASRec-style next-item model).

No counterpart exists in the reference (it has no sequence models —
SURVEY.md §5); this is the framework's long-context model family,
extending the template set the same way the two-tower target does
(BASELINE config 5). Architecture follows the public SASRec formulation
(Kang & McAuley 2018): item + position embeddings, a stack of causal
self-attention + pointwise-FFN blocks with pre-layernorm and residuals,
next-item scoring by inner product with the (tied) item embedding table.

TPU mapping:

- the whole training run is ONE jitted program: `lax.scan` over steps of
  `lax.scan` over a fixed epoch of batches — no per-step dispatch;
- attention is pluggable: local (single chip) or **ring attention** over
  a mesh sequence axis (`predictionio_tpu.parallel.ring_attention`) for
  histories too long for one chip's HBM — the same exact math;
- embedding/softmax matmuls hit the MXU in bf16-friendly shapes (dims
  padded to multiples of 128 upstream by the caller where it matters).

Padding convention: item id 0 is PAD; real items are 1..n_items.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np


@dataclass
class SeqRecParams:
    """num_blocks/num_heads/hidden per SASRec defaults; seq_len is the
    model's fixed context window (sequences are left-truncated/padded)."""

    hidden: int = 64
    num_blocks: int = 2
    num_heads: int = 2
    seq_len: int = 64
    # the model is deterministic (no dropout): serving parity and exact
    # ring-vs-local equivalence matter more here than SASRec's 0.2 dropout
    lr: float = 1e-3
    epochs: int = 20
    batch_size: int = 128
    l2: float = 0.0
    seed: int = 7
    # mid-train checkpoint/resume (SURVEY.md §5): save params +
    # optimizer state every N epochs; a restarted train with the same
    # dir resumes from the newest checkpoint and (batches are fixed per
    # seed) produces the same final model as an uninterrupted run. None
    # disables. The iteration loop then runs in blocks of
    # ``checkpoint_every`` epochs (each block one compiled program).
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1


def init_params(n_items: int, p: SeqRecParams) -> Dict:
    """Parameter pytree. Vocabulary row 0 is PAD (zeroed, masked out)."""
    rng = np.random.default_rng(p.seed)
    d, V = p.hidden, n_items + 1

    def dense(shape, scale=None):
        scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    item_emb = dense((V, d), 0.02)
    item_emb[0] = 0.0
    params = {
        "item_emb": item_emb,
        "pos_emb": dense((p.seq_len, d), 0.02),
        "blocks": [],
        "ln_f": {"g": np.ones(d, np.float32), "b": np.zeros(d, np.float32)},
    }
    for _ in range(p.num_blocks):
        params["blocks"].append({
            "ln1": {"g": np.ones(d, np.float32), "b": np.zeros(d, np.float32)},
            "wq": dense((d, d)), "wk": dense((d, d)), "wv": dense((d, d)),
            "wo": dense((d, d)),
            "ln2": {"g": np.ones(d, np.float32), "b": np.zeros(d, np.float32)},
            "w1": dense((d, 4 * d)), "b1": np.zeros(4 * d, np.float32),
            "w2": dense((4 * d, d)), "b2": np.zeros(d, np.float32),
        })
    return params


def _ln(x, g, b, eps=1e-6):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def forward(params: Dict, seqs, p: SeqRecParams, mesh=None,
            seq_axis: str = "data"):
    """[B, S] int item ids (0=pad) → [B, S, d] contextual states.

    ``mesh`` routes attention through ring attention over ``seq_axis``
    (S must divide by the axis size); None = local attention.
    """
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.parallel.ring_attention import (
        attention_reference,
        ring_attention,
    )

    B, S = seqs.shape
    d, H = p.hidden, p.num_heads
    Dh = d // H
    k_mask = seqs > 0            # [B, S]: pad positions never serve as keys
    mask = k_mask[..., None]     # [B, S, 1]

    x = params["item_emb"][seqs] * np.sqrt(d) + params["pos_emb"][None, :S]
    x = x * mask

    for blk in params["blocks"]:
        h = _ln(x, blk["ln1"]["g"], blk["ln1"]["b"])
        q = (h @ blk["wq"]).reshape(B, S, H, Dh)
        k = (h @ blk["wk"]).reshape(B, S, H, Dh)
        v = (h @ blk["wv"]).reshape(B, S, H, Dh)
        if mesh is not None:
            att = ring_attention(q, k, v, mesh=mesh, axis=seq_axis,
                                 causal=True, k_mask=k_mask)
        else:
            att = attention_reference(q, k, v, causal=True, k_mask=k_mask)
        x = x + att.reshape(B, S, d) @ blk["wo"]
        h = _ln(x, blk["ln2"]["g"], blk["ln2"]["b"])
        x = x + jax.nn.relu(h @ blk["w1"] + blk["b1"]) @ blk["w2"] + blk["b2"]
        x = x * mask
    return _ln(x, params["ln_f"]["g"], params["ln_f"]["b"]) * mask


def _loss(params, seqs, targets, p: SeqRecParams, mesh=None, l2=None):
    """Mean masked cross-entropy of next-item prediction.

    targets[b, t] = seqs[b, t+1]-style shifted ids, 0 where padded.
    """
    import jax
    import jax.numpy as jnp

    states = forward(params, seqs, p, mesh=mesh)  # [B, S, d]
    logits = states @ params["item_emb"].T        # [B, S, V] tied weights
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt_logp = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    m = (targets > 0).astype(jnp.float32)
    loss = -(tgt_logp * m).sum() / jnp.maximum(m.sum(), 1.0)
    # l2 (when given) is a TRACED scalar — the compiled trainer passes
    # it so an eval grid over regularization shares one executable;
    # p.l2 is the Python-static path for direct callers
    reg = p.l2 if l2 is None else l2
    if l2 is not None or p.l2:
        loss = loss + reg * sum(
            jnp.sum(w ** 2) for w in jax.tree.leaves(params))
    return loss


def make_training_batches(sequences, p: SeqRecParams, seed: int = 0
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side prep: list of per-user item-id lists → fixed-shape
    (inputs [N, S], targets [N, S]) with left-padding, shuffled and
    padded to a whole number of batches."""
    S = p.seq_len
    xs, ys = [], []
    for seq in sequences:
        seq = [i for i in seq if i > 0]
        if len(seq) < 2:
            continue
        seq = seq[-(S + 1):]
        inp, tgt = seq[:-1], seq[1:]
        pad = S - len(inp)
        xs.append(np.pad(inp, (pad, 0)))
        ys.append(np.pad(tgt, (pad, 0)))
    if not xs:
        raise ValueError("no trainable sequences (all shorter than 2)")
    X = np.asarray(xs, np.int32)
    Y = np.asarray(ys, np.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(X))
    X, Y = X[order], Y[order]
    bs = min(p.batch_size, len(X))
    n_batches = -(-len(X) // bs)
    padn = n_batches * bs - len(X)
    if padn:  # repeat leading rows: keeps shapes static, loss still masked
        X = np.concatenate([X, X[:padn]])
        Y = np.concatenate([Y, Y[:padn]])
    return X.reshape(n_batches, bs, S), Y.reshape(n_batches, bs, S)


def _make_tx():
    """The optimizer, constructed ONE way everywhere so checkpointed
    state and the compiled trainer always agree on structure.
    learning_rate is a placeholder: callers set
    ``opt_state.hyperparams["learning_rate"]`` per candidate."""
    import optax

    return optax.inject_hyperparams(optax.adam)(learning_rate=1e-3)


@functools.lru_cache(maxsize=8)
def _train_compiled(hidden: int, num_blocks: int, num_heads: int,
                    seq_len: int, epochs: int, use_l2: bool, mesh=None):
    """Jitted trainer keyed on GEOMETRY (array shapes are traced):
    ``lr`` rides inside the optimizer state (optax.inject_hyperparams)
    and ``l2`` is a traced scalar, so a `pio eval` grid over either
    shares one executable. ``use_l2`` is static: the common l2=0 path
    must not pay the full parameter-norm reduction for a multiply by a
    traced zero. ``mesh`` routes attention through the
    sequence-parallel ring path. Signature:
    ``train(params, opt_state, X, Y, l2)``."""
    import jax

    import optax

    p = SeqRecParams(hidden=hidden, num_blocks=num_blocks,
                     num_heads=num_heads, seq_len=seq_len, l2=0.0)
    tx = _make_tx()

    def train(params, opt_state, X, Y, l2):
        def batch_step(carry, xy):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(_loss)(
                params, xy[0], xy[1], p, mesh,
                l2 if use_l2 else None)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        def epoch(carry, _):
            carry, losses = jax.lax.scan(batch_step, carry, (X, Y))
            return carry, losses.mean()

        (params, opt_state), losses = jax.lax.scan(
            epoch, (params, opt_state), None, length=epochs)
        return params, opt_state, losses

    return jax.jit(train)


def seq_rec_train(sequences, n_items: int, p: SeqRecParams, mesh=None,
                  seq_axis: str = "data") -> Tuple[Dict, np.ndarray]:
    """Train on per-user item-id sequences; returns (params, loss/epoch).

    The full run is one compiled program (scan over epochs of scan over
    batches) — zero host round-trips after dispatch. ``mesh`` shards
    attention over ``seq_axis`` via ring attention (requires
    ``seq_len %% axis size == 0``); incompatible meshes fall back to
    local attention rather than failing the train.
    """
    import jax
    import jax.numpy as jnp

    import optax

    if mesh is not None and (
            seq_axis not in mesh.axis_names
            or p.seq_len % mesh.shape[seq_axis]):
        mesh = None
    X, Y = make_training_batches(sequences, p, seed=p.seed)
    params = jax.tree.map(jnp.asarray, init_params(n_items, p))

    def compiled(n_epochs: int):
        return _train_compiled(p.hidden, p.num_blocks, p.num_heads,
                               p.seq_len, int(n_epochs), bool(p.l2), mesh)

    opt_state = _make_tx().init(params)
    # the candidate's lr enters THROUGH the optimizer state (a traced
    # leaf); l2 is a traced argument — neither recompiles the program
    opt_state.hyperparams["learning_rate"] = jnp.float32(p.lr)
    l2 = jnp.float32(p.l2)

    def run_block(state, n):
        params, opt_state, losses = compiled(n)(
            state["params"], state["opt_state"], X, Y, l2)
        return {"params": params, "opt_state": opt_state}, np.asarray(losses)

    def set_lr(state):
        state["opt_state"].hyperparams["learning_rate"] = jnp.float32(p.lr)

    state, loss_parts = run_epoch_blocks(
        p.epochs, p.checkpoint_dir, p.checkpoint_every,
        {"params": params, "opt_state": opt_state}, run_block, set_lr)
    # losses cover only the epochs run in THIS process (a resumed run
    # reports the remainder)
    return state["params"], (np.concatenate(loss_parts) if loss_parts
                             else np.zeros(0, np.float32))


def run_epoch_blocks(epochs: int, checkpoint_dir: Optional[str],
                     checkpoint_every: int, state: Dict, run_block, set_lr
                     ) -> Tuple[Dict, list]:
    """The epoch loop every neural trainer here shares: ``run_block(
    state, n) -> (state, record)`` runs ``n`` epochs as one compiled
    program; returns the final state and the records of the blocks run
    in THIS process. ``state`` is a dict of device pytrees that fully
    determines the remainder (batches are fixed per seed), so resume
    reproduces the uninterrupted run.

    Without ``checkpoint_dir`` all epochs are one block. With it, the
    loop runs in blocks of ``checkpoint_every`` epochs and writes a
    checkpoint BETWEEN blocks, never after the last: the model save
    follows it at once and the workflow deletes the directory on
    completion, so that write (a fetch of params + optimizer state
    with the chip idle) would be for nothing. ``set_lr(state)`` makes
    THIS run's learning rate win over a restored one."""
    import jax
    import jax.numpy as jnp

    if not checkpoint_dir:
        state, record = run_block(state, epochs)
        return state, [record]

    from predictionio_tpu.utils.checkpoint import (CheckpointGeometryError,
                                                   TrainCheckpointer)

    def to_host(state):
        return jax.tree.map(np.asarray, state)

    ckpt = TrainCheckpointer(checkpoint_dir)
    start = 0
    if ckpt.latest_step() is not None:
        try:
            # newest→oldest walk: a crash-truncated newest save falls
            # back to the previous good step instead of a full retrain
            restored, latest = ckpt.restore_latest_compatible(to_host(state))
            state = jax.tree.map(jnp.asarray, restored)
            # THIS run's lr wins over the checkpointed one (annealing
            # restarts must not silently keep the old rate)
            set_lr(state)
            start = min(int(latest), epochs)
        except CheckpointGeometryError:
            # CONFIRMED stale (different geometry) → fresh start; WIPE
            # the dir, else the fresh run's lower step numbers stay
            # shadowed by the stale latest_step and every future resume
            # restores the bad checkpoint again. Transient read errors
            # propagate — wiping on those destroys valid checkpoints.
            import warnings

            warnings.warn(
                "seq_rec checkpoints are stale (geometry/format change) — wiped; training restarts from scratch",
                RuntimeWarning)
            ckpt.clear()
    records = []
    epoch = start
    while epoch < epochs:
        n = min(max(1, checkpoint_every), epochs - epoch)
        state, record = run_block(state, n)
        records.append(record)
        epoch += n
        if epoch < epochs:
            ckpt.save(epoch, to_host(state))
    ckpt.close()
    return state, records


@functools.lru_cache(maxsize=8)
def _scores_compiled(hidden: int, num_blocks: int, num_heads: int,
                     seq_len: int):
    """Jitted serving path (the p50-critical call): one dispatch per
    query batch instead of dozens of eager ops."""
    import jax

    p = SeqRecParams(hidden=hidden, num_blocks=num_blocks,
                     num_heads=num_heads, seq_len=seq_len)

    def score(params, x):
        states = forward(params, x, p)          # [B, S, d]
        return states[:, -1] @ params["item_emb"].T  # [B, V]

    return jax.jit(score)


def seq_rec_scores(params: Dict, history, p: SeqRecParams) -> np.ndarray:
    """Scores over the full vocabulary for the NEXT item after ``history``
    (a list of item ids); [V] numpy array, PAD row = -inf."""
    S = p.seq_len
    seq = [i for i in history if i > 0][-S:]
    x = np.zeros((1, S), np.int32)
    if seq:
        x[0, S - len(seq):] = seq
    score = _scores_compiled(p.hidden, p.num_blocks, p.num_heads, p.seq_len)
    logits = np.array(score(params, x)[0])  # writable host copy
    logits[0] = -np.inf
    return logits
