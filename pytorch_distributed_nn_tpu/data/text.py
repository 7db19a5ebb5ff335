"""Synthetic masked-LM data pipeline (BERT-base stretch config).

The reference's data layer is torchvision image datasets only (reference:
src/util.py:21-106); the BERT-base MLM stretch config (BASELINE.json) needs
a token pipeline. With zero egress in this environment, the corpus is
synthetic but *structured*: token streams are drawn from a fixed random
bigram chain, so an MLM model has real statistical signal to learn (masked-
token accuracy well above chance) — good enough for convergence smoke tests
and for benchmarking tokens/sec, which is corpus-independent.

Special ids follow BERT conventions: 0=[PAD] 1=[CLS] 2=[SEP] 3=[MASK];
real tokens are ids >= NUM_SPECIAL.
"""

from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np

from pytorch_distributed_nn_tpu.observability.spans import span
from pytorch_distributed_nn_tpu.ops.metrics import IGNORE_INDEX

PAD_ID, CLS_ID, SEP_ID, MASK_ID = 0, 1, 2, 3
NUM_SPECIAL = 4


class BigramCorpus:
    """Deterministic synthetic corpus: a sparse random bigram chain.

    Each token has ``branching`` plausible successors with Zipf-ish weights;
    sequences are random walks. Entropy is low enough that a small model
    reaches >50% masked accuracy within a few hundred steps.
    """

    def __init__(self, vocab_size: int, branching: int = 8, seed: int = 0):
        assert vocab_size > NUM_SPECIAL + branching
        self.vocab_size = vocab_size
        rng = np.random.RandomState(seed)
        n_real = vocab_size - NUM_SPECIAL
        # successors[t] = candidate next tokens for real token t
        self.successors = rng.randint(
            0, n_real, size=(n_real, branching)
        ).astype(np.int32)
        w = 1.0 / np.arange(1, branching + 1)
        self.succ_probs = w / w.sum()
        self.branching = branching

    def sample_tokens(self, rng: np.random.RandomState, batch: int, length: int):
        """(batch, length) int32 token ids: [CLS] walk... [SEP]."""
        n_real = self.vocab_size - NUM_SPECIAL
        out = np.empty((batch, length), np.int32)
        out[:, 0] = CLS_ID
        cur = rng.randint(0, n_real, size=batch)
        for j in range(1, length - 1):
            out[:, j] = cur + NUM_SPECIAL
            choice = rng.choice(self.branching, size=batch, p=self.succ_probs)
            cur = self.successors[cur, choice]
        out[:, length - 1] = SEP_ID
        return out

    def sample_walks(self, rng: np.random.RandomState, batch: int, length: int):
        """(batch, length) int32 real-token ids: a plain random walk of the
        chain, no special tokens (next-token batches). Every draw is made
        at once and only the walk itself loops, so a long sequence costs
        the host ~2 us a position, not a ``rng.choice`` call."""
        n_real = self.vocab_size - NUM_SPECIAL
        choice = rng.choice(
            self.branching, size=(length, batch), p=self.succ_probs)
        out = np.empty((batch, length), np.int32)
        cur = rng.randint(0, n_real, size=batch)
        for j in range(length):
            out[:, j] = cur
            cur = self.successors[cur, choice[j]]
        return out + NUM_SPECIAL


def mask_tokens(
    tokens: np.ndarray,
    rng: np.random.RandomState,
    vocab_size: int,
    mask_prob: float = 0.15,
) -> Tuple[np.ndarray, np.ndarray]:
    """BERT-style masking: of the 15% selected, 80% → [MASK], 10% → random,
    10% → unchanged. Returns (inputs, labels); labels are IGNORE_INDEX at
    unselected positions. Special tokens are never selected.
    """
    selectable = tokens >= NUM_SPECIAL
    sel = (rng.random_sample(tokens.shape) < mask_prob) & selectable
    labels = np.where(sel, tokens, IGNORE_INDEX).astype(np.int32)

    inputs = tokens.copy()
    r = rng.random_sample(tokens.shape)
    to_mask = sel & (r < 0.8)
    to_rand = sel & (r >= 0.8) & (r < 0.9)
    inputs[to_mask] = MASK_ID
    inputs[to_rand] = rng.randint(
        NUM_SPECIAL, vocab_size, size=int(to_rand.sum())
    ).astype(np.int32)
    return inputs, labels


class MLMBatches:
    """Infinite iterator of (inputs, labels) MLM batches.

    Mirrors the image loader's role (data/loader.py) for the text path:
    host-side numpy generation, ready for `jax.device_put` with a
    (data[, seq])-sharded NamedSharding.
    """

    def __init__(
        self,
        vocab_size: int = 1024,
        seq_len: int = 128,
        batch_size: int = 32,
        seed: int = 0,
        mask_prob: float = 0.15,
        branching: int = 8,
        corpus_seed: Optional[int] = None,
    ):
        # The corpus (the bigram transition table — i.e. "the language") and
        # the sampling stream are seeded independently: train and eval
        # loaders must share corpus_seed while drawing different streams,
        # otherwise eval measures a different language than was trained.
        if corpus_seed is None:
            corpus_seed = seed
        self.corpus = BigramCorpus(
            vocab_size, branching=branching, seed=corpus_seed
        )
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.batch_size = batch_size
        self.mask_prob = mask_prob
        self._seed = seed
        self._counter = 0

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        return self

    def _stream_rng(self, index: int) -> np.random.RandomState:
        # Counter-based stream: batch #i is a pure function of
        # (seed, i) via an independent SeedSequence spawn, so the stream
        # is O(1)-seekable (`skip`) — a resumed run continues from the
        # exact stream position instead of replaying batch 0 (the round-4
        # BERT-base run's supervisor restarts exposed the replay).
        ss = np.random.SeedSequence((self._seed + 1, index))
        # Seed the generator with the FULL SeedSequence state: collapsing
        # to one uint32 word would birthday-collide distinct batch
        # indices (~2% over a 14k-step run) into byte-identical batches.
        return np.random.RandomState(np.random.MT19937(ss))

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        rng = self._stream_rng(self._counter)
        self._counter += 1
        toks = self.corpus.sample_tokens(rng, self.batch_size, self.seq_len)
        return mask_tokens(toks, rng, self.vocab_size, self.mask_prob)

    def skip(self, n: int) -> None:
        """O(1) fast-forward of the training stream (resume support)."""
        self._counter += int(n)

    # Iterator-state contract (docs/data.md): the stream is counter-based,
    # so the whole position is one integer. Captured in every checkpoint's
    # `model_step_<N>.data.json` sidecar (training/checkpoint.py) so
    # --resume continues the exact stream even when the checkpoint step
    # and the stream position have diverged (e.g. a run that advanced the
    # loader outside the step loop).
    STATE_FORMAT = "pdtn-mlm-state-v1"

    def state(self) -> dict:
        return {"format": self.STATE_FORMAT, "kind": "mlm",
                "counter": int(self._counter)}

    def restore(self, state: dict) -> None:
        if state.get("kind") != "mlm":
            raise ValueError(
                f"iterator state is kind {state.get('kind')!r}, expected "
                "'mlm'"
            )
        self._counter = int(state["counter"])

    # Canonical draw width for the eval token stream. The stream is drawn in
    # fixed (_EVAL_CHUNK, L) chunks and re-sliced to the caller's batch
    # size, so eval sequence #i is a function of (seed, corpus, seq_len,
    # mask_prob) ONLY — never of batch geometry. Two processes whose batch
    # sizes differ (e.g. the trainer rounds --test-batch-size down to a
    # multiple of the worker count, trainer.py, while a decoupled evaluator
    # does not) still score the identical sequence stream prefix. Width 512
    # keeps the per-position sampling loop cheap at default eval sizes
    # (64 batches x 1000 sequences) without costing the invariant.
    _EVAL_CHUNK = 512

    def eval_set(self, n_batches: int):
        """A FIXED eval set: ``n_batches`` (inputs, labels) batches drawn
        from a dedicated rng seeded only by the loader config — the same
        batches every call, independent of how far the training stream
        (`__next__`) has advanced, and (via the canonical chunked draw,
        `_EVAL_CHUNK`) independent of ``batch_size`` itself: sequence #i
        is identical for every batch geometry. This is the MLM analogue
        of the image path's frozen test split: every reported accuracy is
        over the same ``n_batches * batch_size`` sequences (the reference
        always evaluated its full fixed test set,
        src/distributed_evaluator.py:90-106).
        """
        rng = np.random.RandomState(self._seed + 7919)
        total = n_batches * self.batch_size
        if total <= 0:  # --eval-batches 0 = eval pass is a no-op
            return []
        xs, ys = [], []
        for _ in range(-(-total // self._EVAL_CHUNK)):
            toks = self.corpus.sample_tokens(
                rng, self._EVAL_CHUNK, self.seq_len
            )
            x, y = mask_tokens(toks, rng, self.vocab_size, self.mask_prob)
            xs.append(x)
            ys.append(y)
        x = np.concatenate(xs)[:total]
        y = np.concatenate(ys)[:total]
        bs = self.batch_size
        return [
            (x[i * bs:(i + 1) * bs], y[i * bs:(i + 1) * bs])
            for i in range(n_batches)
        ]


def next_token_labels(tokens: np.ndarray, depth: int = 1) -> np.ndarray:
    """Labels of a causal LM: position t predicts token t + 1; the last
    position has nothing to predict (``IGNORE_INDEX``). With ``depth`` > 1
    (a model with next-token prediction modules) the labels gain a last
    axis: ``[..., j]`` is token t + 1 + j, ``IGNORE_INDEX`` where that lies
    past the sequence."""
    length = tokens.shape[1]
    labels = np.full(tokens.shape + (depth,), IGNORE_INDEX, tokens.dtype)
    for j in range(depth):
        labels[:, :length - 1 - j, j] = tokens[:, 1 + j:]
    return labels[..., 0] if depth == 1 else labels


class NextTokenBatches(MLMBatches):
    """Infinite iterator of (tokens, labels) next-token batches over the
    same bigram corpus, stream and state contract as `MLMBatches`: the
    inputs are the walk itself (nothing masked), the labels the tokens
    shifted by one with ``IGNORE_INDEX`` last — so the MLM loss and
    metrics functions are the causal LM's too, over every position but
    the last. ``depth`` > 1 gives the labels of a model that also predicts
    further ahead (``next_token_labels``). ``dataset='NextTokenSynth'``."""

    def __init__(self, *args, depth: int = 1, **kw):
        super().__init__(*args, **kw)
        self.depth = depth

    def _pair(self, rng: np.random.RandomState, batch: int):
        toks = self.corpus.sample_walks(rng, batch, self.seq_len)
        return toks, next_token_labels(toks, self.depth)

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        rng = self._stream_rng(self._counter)
        self._counter += 1
        return self._pair(rng, self.batch_size)

    def eval_set(self, n_batches: int):
        """``n_batches`` fixed batches, the same every call (the draw is
        per batch: next-token evaluation is only ever sized in whole
        batches of this loader)."""
        rng = np.random.RandomState(self._seed + 7919)
        return [self._pair(rng, self.batch_size) for _ in range(n_batches)]


#: TrainConfig.dataset -> the batch iterator a text model trains on
TEXT_DATASETS = {"MLMSynth": MLMBatches, "NextTokenSynth": NextTokenBatches}


class MLMLoader:
    """DataLoader-interface adapter over `MLMBatches` for the Trainer.

    Presents the image loader's surface (``next_batch`` / ``steps_per_epoch``
    / ``epoch_batches`` / ``close`` — data/loader.py) so the Trainer drives
    text and vision identically. The synthetic corpus is infinite, so
    ``steps_per_epoch`` is a nominal epoch length.

    ``epoch_batches`` (the eval pass) iterates a FIXED deterministic eval
    set of ``eval_batches`` batches (`MLMBatches.eval_set`), device-put
    once and cached — every `Trainer.evaluate()` / polling-evaluator pass
    scores the same ``eval_sequences`` sequences, and two loaders built
    with the same config score identical data. Round 2 drew 4 fresh
    stream batches per pass (~4×B sequences, different every call);
    the round-3 verdict (item 7) asked for the reference's fixed-test-set
    semantics with a documented sequence count.
    """

    def __init__(
        self,
        batches: MLMBatches,
        sharding=None,
        steps_per_epoch: int = 100,
        eval_batches: int = 64,
    ):
        self._batches = batches
        self._sharding = sharding
        self.steps_per_epoch = steps_per_epoch
        self._eval_batches = eval_batches
        self._eval_cache = None
        self.last_wait_ms = 0.0

    @property
    def eval_sequences(self) -> int:
        """Number of sequences every eval pass scores (document this next
        to any reported MLM accuracy)."""
        return self._eval_batches * self._batches.batch_size

    def skip(self, n: int) -> None:
        """Fast-forward the training stream by ``n`` batches (O(1)) —
        the sidecar-less resume fallback (the Trainer prefers
        ``restore()`` of a checkpointed ``state()``)."""
        self._batches.skip(n)

    def state(self) -> dict:
        """Serializable iterator state (the stream counter) — captured in
        checkpoints so --resume stops replaying MLM batches."""
        return self._batches.state()

    def restore(self, state: dict) -> None:
        self._batches.restore(state)

    def __len__(self):
        return self.steps_per_epoch * self._batches.batch_size

    def _put(self, arr: np.ndarray):
        import jax

        if self._sharding is None:
            return arr
        return jax.device_put(arr, self._sharding)

    def next_batch(self):
        # input-wait accounting (docs/observability.md): this loader
        # generates on the calling thread, so the whole generation is
        # wait; the dispatch to the device (input/put) is not
        with span("input/produce") as produce:
            x, y = next(self._batches)
        self.last_wait_ms = produce.seconds * 1000
        with span("input/put"):
            return self._put(x), self._put(y)

    def epoch_batches(self):
        # The eval set stays device-resident for the loader's lifetime
        # (~260 MB at eval defaults, 1.6% of a 16 GB chip): nothing is
        # re-uploaded per eval pass; `close()` releases the cache when the
        # run ends.
        if self._eval_cache is None:
            self._eval_cache = [
                (self._put(x), self._put(y))
                for x, y in self._batches.eval_set(self._eval_batches)
            ]
        yield from self._eval_cache

    def close(self):
        self._eval_cache = None
