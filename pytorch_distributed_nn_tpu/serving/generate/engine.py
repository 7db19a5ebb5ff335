"""Generative inference engine: a causal decoder behind pre-traced
prefill/insert/decode jit families with bucketed KV-cache pools.

The zero-retrace discipline of the single-pass engine
(``serving/engine.py``) extends to TWO phases here, each with its own
padded-bucket family, all pre-traced at :meth:`GenerativeEngine.warmup`:

- **prefill** — one jitted forward per PROMPT length bucket (batch 1,
  the largest-fitting-bucket admission policy): pads the prompt, runs
  the causal forward, returns the last valid position's logits (the
  first generated token's distribution) and the per-layer K/V
  projections;
- **insert** — one jitted scatter per (prompt bucket, cache bucket)
  pair: writes a prefill's K/V panel into a pool page;
- **decode** — one jitted step per (batch bucket, cache bucket) pair:
  gathers the batch's pages from the pool, writes each row's new token
  K/V at its own position, runs single-position attention + the
  per-token MLP/head, scatters the updated pages back. The pool rides
  OUTSIDE the jit as a donated operand — cache state is explicit
  engine state, never a flax mutable collection, so a params swap can
  never invalidate a trace.

``retraces()`` counts executables across all three families; the test
suite and the chaos ``generate`` scenario assert it stays 0 across mixed
prompt lengths, generation lengths and hot swaps.

Hot swap (docs/serving.md "Generative serving"): :meth:`swap` installs
new weights like the single-pass engine — but a decoder also carries
per-sequence K/V computed with the OLD weights. Every swap bumps
``epoch``; the pools' slot ledger fences pages by epoch
(``kvcache.KVCachePool.checkout`` refuses stale pages), and the
scheduler re-prefills fenced sequences under the new weights — no token
is ever generated against mixed-version state. ``shadow`` gives a
canary its own weights AND its own pools behind the same executables:
canary isolation is by construction, not by fencing.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_nn_tpu.serving.generate.kvcache import KVCachePool

logger = logging.getLogger(__name__)

#: decode batch buckets: how many sequences one decode step advances
DEFAULT_DECODE_BATCH_BUCKETS = (1, 2, 4, 8)

#: smallest cache bucket — below this the bucket table would outnumber
#: the sequences it serves
_MIN_SEQ_BUCKET = 16


def default_seq_buckets(max_len: int) -> Tuple[int, ...]:
    """Powers of two from ``_MIN_SEQ_BUCKET`` up to (and always
    including) ``max_len`` — the total-length (prompt + generation)
    bucket grid, shared by the prompt buckets."""
    from pytorch_distributed_nn_tpu.serving.engine import length_buckets

    out = tuple(
        b for b in length_buckets(max_len)
        if b >= min(_MIN_SEQ_BUCKET, max_len)
    )
    return out or (max_len,)


class StaleBatchEpoch(RuntimeError):
    """A swap landed between the scheduler's fence round and the decode
    dispatch: the batch was formed under an epoch that is no longer
    current. Nothing stale was read — the whole batch is refused so the
    caller re-validates — so this is NOT a fence violation."""


class GenerativeEngine:
    """Loads a causal-decoder artifact and serves prefill + per-token
    decode over bucketed KV-cache pools."""

    def __init__(
        self,
        artifact_dir: str,
        batch_buckets: Sequence[int] = DEFAULT_DECODE_BATCH_BUCKETS,
        seq_buckets: Optional[Sequence[int]] = None,
        prompt_buckets: Optional[Sequence[int]] = None,
        pool_slots: Optional[int] = None,
        decode_attn: str = "exact",
    ):
        from pytorch_distributed_nn_tpu.models import (
            build_model,
            is_generative_model,
        )
        from pytorch_distributed_nn_tpu.serving.artifact import load_artifact

        if not batch_buckets or list(batch_buckets) != sorted(set(batch_buckets)):
            raise ValueError(
                f"batch_buckets must be strictly increasing, got "
                f"{batch_buckets!r}"
            )
        if decode_attn not in ("exact", "fast", "pallas"):
            raise ValueError(
                f"unknown decode_attn {decode_attn!r}; expected "
                "exact|fast|pallas"
            )
        self.manifest, params, _ = load_artifact(artifact_dir)
        network = self.manifest["network"]
        if not is_generative_model(network):
            raise ValueError(
                f"artifact network {network!r} is not a causal decoder — "
                "the generative engine serves GENERATIVE_MODELS only "
                "(serve the single-pass engine instead)"
            )
        self.artifact_dir = artifact_dir
        decode_attn_fn = None
        if decode_attn == "fast":
            from pytorch_distributed_nn_tpu.models.transformer import (
                decode_attention_fast,
            )

            decode_attn_fn = decode_attention_fast
        elif decode_attn == "pallas":
            from pytorch_distributed_nn_tpu.ops.pallas_kernels import (
                pallas_decode_attention,
            )

            decode_attn_fn = pallas_decode_attention
        self.decode_attn = decode_attn
        self.model = build_model(
            network, self.manifest["num_classes"],
            decode_attn_fn=decode_attn_fn,
            **self.manifest.get("model_kw", {}),
        )
        cfg = self.model.config
        self.vocab_size = int(cfg.vocab_size)
        self.max_len = int(cfg.max_len)
        self.num_heads = int(cfg.num_heads)
        self.head_dim = int(cfg.d_model // cfg.num_heads)
        self.num_layers = int(cfg.num_layers)
        self.cache_dtype = cfg.dtype

        self.params = jax.device_put(params)
        self._weights_lock = threading.Lock()
        self.swaps = 0
        #: weight-swap epoch — the KV-page fence token (kvcache ledger)
        self.epoch = 0

        self.batch_buckets = tuple(int(b) for b in batch_buckets)
        self.seq_buckets = tuple(
            int(s) for s in (seq_buckets or default_seq_buckets(self.max_len))
        )
        if self.seq_buckets[-1] > self.max_len:
            raise ValueError(
                f"seq bucket {self.seq_buckets[-1]} exceeds the model "
                f"max_len {self.max_len}"
            )
        self.prompt_buckets = tuple(
            int(s) for s in (prompt_buckets or self.seq_buckets)
        )
        self.pool_slots = int(pool_slots or 2 * self.batch_buckets[-1])

        # slot ledgers + the pool ARRAYS (one scratch page past the
        # usable slots — decode pads batches with it)
        self.pools: Dict[int, KVCachePool] = {}
        self._pool_kv: Dict[int, tuple] = {}
        for s in self.seq_buckets:
            self.pools[s] = KVCachePool(s, self.pool_slots)
            self._pool_kv[s] = tuple(
                (
                    jnp.zeros(
                        (self.pool_slots + 1, s, self.num_heads,
                         self.head_dim), self.cache_dtype,
                    ),
                    jnp.zeros(
                        (self.pool_slots + 1, s, self.num_heads,
                         self.head_dim), self.cache_dtype,
                    ),
                )
                for _ in range(self.num_layers)
            )

        model = self.model

        def _prefill_fn(params, tokens, length):
            # tokens (1, Sp), length (1,) — mask pads, take the last
            # VALID position's logits (first generated token's dist)
            Sp = tokens.shape[1]
            mask = (
                jnp.arange(Sp)[None, :] < length[:, None]
            ).astype(jnp.int32)
            logits, kvs = model.apply(
                {"params": params}, tokens, mask=mask, return_kv=True,
            )
            last = jnp.take_along_axis(
                logits, (length - 1)[:, None, None], axis=1,
            )[:, 0]
            return last, kvs

        def _insert_fn(pool, kvs, slot):
            def put(p, n):
                return jax.lax.dynamic_update_slice(
                    p, n.astype(p.dtype), (slot, 0, 0, 0)
                )

            return jax.tree.map(put, pool, kvs)

        def _decode_fn(params, pool, slots, tokens, positions):
            gathered = jax.tree.map(lambda a: a[slots], pool)
            logits, new_kv = model.apply(
                {"params": params}, tokens[:, None],
                cache=gathered, positions=positions,
            )
            new_pool = jax.tree.map(
                lambda p, n: p.at[slots].set(n.astype(p.dtype)),
                pool, new_kv,
            )
            return logits, new_pool

        self._prefill_j = jax.jit(_prefill_fn)
        self._insert_j = jax.jit(_insert_fn, donate_argnums=(0,))
        self._decode_j = jax.jit(_decode_fn, donate_argnums=(1,))
        self._warm_cache: Optional[int] = None

        # counters (obs/stats surface)
        self.prefills = 0
        self.decode_steps = 0
        self.decode_rows = 0  # live rows across decode steps (occupancy)
        self.tokens_generated = 0
        # decode attempted on pages already stale when the batch was
        # formed (a mid-round swap refuses via StaleBatchEpoch instead)
        self.fence_violations = 0

    # -- identity ----------------------------------------------------------

    @property
    def version(self) -> str:
        from pytorch_distributed_nn_tpu.serving.artifact import (
            artifact_version,
        )

        return artifact_version(self.manifest)

    @property
    def identity(self) -> dict:
        src = self.manifest.get("source") or {}
        return {
            "version": self.version,
            "train_dir": src.get("train_dir"),
            "step": src.get("step"),
            "quantize": self.manifest.get("quantize", "none"),
            "network": self.manifest.get("network"),
            "generative": True,
        }

    # -- bucket policy -----------------------------------------------------

    def select_prompt_bucket(self, length: int) -> int:
        for s in self.prompt_buckets:
            if length <= s:
                return s
        raise ValueError(
            f"prompt of {length} tokens exceeds the largest prompt "
            f"bucket {self.prompt_buckets[-1]}"
        )

    def select_seq_bucket(self, total: int) -> int:
        """Smallest cache bucket >= prompt + max_new_tokens."""
        for s in self.seq_buckets:
            if total <= s:
                return s
        raise ValueError(
            f"prompt + max_new_tokens of {total} exceeds the largest "
            f"cache bucket {self.seq_buckets[-1]}"
        )

    # -- tracing -----------------------------------------------------------

    def _cache_size(self) -> Optional[int]:
        total = 0
        for fn in (self._prefill_j, self._insert_j, self._decode_j):
            hook = getattr(fn, "_cache_size", None)
            if not callable(hook):
                return None
            try:
                total += int(hook())
            except Exception:
                return None
        return total

    def warmup(self) -> float:
        """Pre-trace EVERY (phase, bucket) family so steady-state
        generation never compiles. Returns warmup wall seconds."""
        t0 = time.perf_counter()
        params = self.params
        kvs_by_bucket = {}
        for sp in self.prompt_buckets:
            tokens = jnp.zeros((1, sp), jnp.int32)
            last, kvs = self._prefill_j(params, tokens,
                                        jnp.ones((1,), jnp.int32))
            jax.block_until_ready(last)
            kvs_by_bucket[sp] = kvs
        for s in self.seq_buckets:
            scratch = jnp.asarray(self.pools[s].scratch, jnp.int32)
            for sp in self.prompt_buckets:
                if sp > s:
                    continue
                # scratch-page insert: warms the (sp, s) pair without
                # touching a live page
                self._pool_kv[s] = self._insert_j(
                    self._pool_kv[s], kvs_by_bucket[sp], scratch
                )
            for b in self.batch_buckets:
                slots = jnp.full((b,), self.pools[s].scratch, jnp.int32)
                toks = jnp.zeros((b,), jnp.int32)
                pos = jnp.zeros((b,), jnp.int32)
                logits, self._pool_kv[s] = self._decode_j(
                    params, self._pool_kv[s], slots, toks, pos
                )
                jax.block_until_ready(logits)
        self._warm_cache = self._cache_size()
        dt = time.perf_counter() - t0
        logger.info(
            "generative warmup: %d prefill / %d cache / %d batch "
            "bucket(s) traced in %.2fs (cache=%s)",
            len(self.prompt_buckets), len(self.seq_buckets),
            len(self.batch_buckets), dt, self._warm_cache,
        )
        return dt

    def retraces(self) -> Optional[int]:
        size = self._cache_size()
        if size is None or self._warm_cache is None:
            return None
        return size - self._warm_cache

    # -- hot swap ----------------------------------------------------------

    def _check_swappable(self, manifest: dict, params) -> None:
        for key in ("network", "num_classes", "model_kw", "input"):
            if manifest.get(key) != self.manifest.get(key):
                raise ValueError(
                    f"refusing swap: artifact {key!r} differs "
                    f"({manifest.get(key)!r} vs serving "
                    f"{self.manifest.get(key)!r})"
                )
        old = jax.tree_util.tree_flatten_with_path(self.params)[0]
        new = jax.tree_util.tree_flatten_with_path(params)[0]
        if len(old) != len(new):
            raise ValueError("refusing swap: params tree shape differs")
        for (pa, a), (pb, b) in zip(old, new):
            if pa != pb or np.shape(a) != np.shape(b) \
                    or np.asarray(a).dtype != np.asarray(b).dtype:
                raise ValueError(
                    f"refusing swap: leaf {jax.tree_util.keystr(pb)} "
                    "mismatches"
                )

    def swap(self, artifact_dir: str) -> str:
        """Install another decoder artifact's weights and FENCE every
        live KV page: the epoch bump makes the pools' ledger refuse
        old-epoch pages at decode time; the scheduler re-prefills those
        sequences under the new weights. Returns the new version."""
        from pytorch_distributed_nn_tpu.serving.artifact import (
            artifact_version,
            load_artifact,
        )

        manifest, params, _ = load_artifact(artifact_dir)
        self._check_swappable(manifest, params)
        params = jax.device_put(params)
        old = self.version
        with self._weights_lock:
            self.manifest = manifest
            self.params = params
            self.artifact_dir = artifact_dir
            self.swaps += 1
            self.epoch += 1
        new = artifact_version(manifest)
        fenced = sum(
            len(p.stale_slots(self.epoch)) for p in self.pools.values()
        )
        logger.info(
            "generative swap #%d: %s -> %s (epoch %d; %d KV page(s) "
            "fenced for re-prefill)", self.swaps, old, new, self.epoch,
            fenced,
        )
        return new

    def shadow(self, artifact_dir: str) -> "GenerativeEngine":
        """A canary engine over the SAME pre-traced executables —
        its own weights, its own pools (a canary's K/V can never mix
        with the stable side's by construction), zero extra compiles."""
        from pytorch_distributed_nn_tpu.serving.artifact import (
            load_artifact,
        )

        manifest, params, _ = load_artifact(artifact_dir)
        self._check_swappable(manifest, params)
        other = object.__new__(GenerativeEngine)
        other.__dict__.update({
            k: v for k, v in self.__dict__.items()
            if k not in ("pools", "_pool_kv")
        })
        other.manifest = manifest
        other.artifact_dir = artifact_dir
        other.params = jax.device_put(params)
        other._weights_lock = threading.Lock()
        other.swaps = 0
        other.epoch = 0
        other.pools = {
            s: KVCachePool(s, self.pool_slots) for s in self.seq_buckets
        }
        other._pool_kv = {
            s: jax.tree.map(jnp.zeros_like, self._pool_kv[s])
            for s in self.seq_buckets
        }
        other.prefills = other.decode_steps = other.decode_rows = 0
        other.tokens_generated = other.fence_violations = 0
        return other

    # -- serving primitives ------------------------------------------------

    def snapshot(self):
        """(params, version, epoch) under the swap barrier — everything
        one prefill or decode step must see consistently."""
        with self._weights_lock:
            return self.params, self.version, self.epoch

    def prefill(self, token_ids: np.ndarray):
        """Run one prompt through the pre-traced prefill bucket.

        Returns ``(last_logits (V,) np, kvs, stats)`` — ``kvs`` is the
        device K/V panel handed straight to :meth:`insert`; ``stats``
        carries the bucket, wall ms and the (version, epoch) snapshot
        the caller must pass to :meth:`insert`/the ledger.
        """
        ln = int(np.shape(token_ids)[0])
        if ln < 1:
            raise ValueError("empty prompt")
        params, version, epoch = self.snapshot()
        t0 = time.perf_counter()
        sp = self.select_prompt_bucket(ln)
        buf = np.zeros((1, sp), np.int32)
        buf[0, :ln] = np.asarray(token_ids, np.int32)
        last, kvs = self._prefill_j(
            params, jnp.asarray(buf), jnp.asarray([ln], jnp.int32)
        )
        logits = np.asarray(last)[0]
        self.prefills += 1
        return logits, kvs, {
            "prompt_bucket": sp,
            "prefill_ms": round((time.perf_counter() - t0) * 1000, 3),
            "version": version,
            "epoch": epoch,
        }

    def insert(self, bucket: int, slot: int, kvs) -> None:
        """Write a prefill's K/V panel into pool page ``slot`` of
        ``bucket`` (pre-traced per (prompt bucket, cache bucket))."""
        self._pool_kv[bucket] = self._insert_j(
            self._pool_kv[bucket], kvs, jnp.asarray(slot, jnp.int32)
        )

    def decode(self, bucket: int, slots: Sequence[int],
               tokens: Sequence[int], positions: Sequence[int],
               expected_epoch: Optional[int] = None):
        """One decode step for up to a batch bucket of sequences in one
        cache bucket: returns ``(logits (n, V) np, stats)``.

        Pads the batch up to the smallest batch bucket with the pool's
        scratch page (garbage K/V goes to a page nobody owns). The
        caller (scheduler) must have epoch-checked the slots via the
        pool ledger — this method re-asserts it and counts any miss as
        a fence violation before refusing. ``expected_epoch`` is the
        epoch the caller validated its batch under: when a swap lands
        between that validation and this dispatch the whole batch is
        refused with :class:`StaleBatchEpoch` WITHOUT convicting the
        ledger — nothing stale was read, the caller just has to
        re-validate — so ``fence_violations`` counts only true contract
        breaches (a batch that was already stale when it was formed).
        """
        n = len(slots)
        if n == 0:
            return np.zeros((0, self.vocab_size), np.float32), {}
        pool = self.pools[bucket]
        params, version, epoch = self.snapshot()
        if expected_epoch is not None and int(expected_epoch) != epoch:
            raise StaleBatchEpoch(
                f"decode batch formed under epoch {int(expected_epoch)} "
                f"but the engine is at epoch {epoch} (swap landed "
                f"mid-round); re-validate and re-prefill"
            )
        for s in slots:
            try:
                pool.checkout(int(s), epoch)
            except RuntimeError:
                self.fence_violations += 1
                raise
        t0 = time.perf_counter()
        bb = None
        for b in self.batch_buckets:
            if n <= b:
                bb = b
                break
        if bb is None:
            raise ValueError(
                f"decode batch of {n} exceeds the largest batch bucket "
                f"{self.batch_buckets[-1]}"
            )
        pad = bb - n
        slot_v = np.asarray(
            list(slots) + [pool.scratch] * pad, np.int32
        )
        tok_v = np.asarray(list(tokens) + [0] * pad, np.int32)
        pos_v = np.asarray(list(positions) + [0] * pad, np.int32)
        logits, self._pool_kv[bucket] = self._decode_j(
            params, self._pool_kv[bucket], jnp.asarray(slot_v),
            jnp.asarray(tok_v), jnp.asarray(pos_v),
        )
        out = np.asarray(logits)[:n]
        dt = (time.perf_counter() - t0) * 1000
        self.decode_steps += 1
        self.decode_rows += n
        self.tokens_generated += n
        return out, {
            "batch": n,
            "batch_bucket": bb,
            "bucket": bucket,
            "decode_ms": round(dt, 3),
            "version": version,
            "epoch": epoch,
        }

    # -- introspection -----------------------------------------------------

    def stats(self) -> dict:
        return {
            "version": self.version,
            "epoch": self.epoch,
            "swaps": self.swaps,
            "prefills": self.prefills,
            "decode_steps": self.decode_steps,
            "tokens_generated": self.tokens_generated,
            "decode_occupancy": (
                self.decode_rows / self.decode_steps
                if self.decode_steps else None
            ),
            "fence_violations": self.fence_violations,
            "retraces": self.retraces(),
            "pools": {s: p.state() for s, p in self.pools.items()},
        }
