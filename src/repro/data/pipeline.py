"""Data pipeline: deterministic synthetic LM task + packed-file loader +
the sync-boundary block prefetcher (DESIGN.md §4).

The synthetic task is a *learnable* noisy-permutation language: token t+1 is
``perm[token_t]`` with probability (1-noise), else uniform.  A small model drives
its CE toward the noise entropy in a few hundred steps, which is exactly what the
GradES reproduction benchmarks need (visible convergence → visible per-matrix
freezing).  Generation is pure numpy off the training thread; batches are sharded
per host (each process materializes only its slice — the multi-host contract).

Batch randomness is keyed by the **absolute step index** (``default_rng((seed,
step))``), not by position in a sequential stream: batch ``i`` is the same
whether the run started at step 0 or resumed from a checkpoint at step ``i`` —
a resumed run never replays earlier batches (the old sequential-stream bug).

:class:`Prefetcher` runs sampling/stacking/``jax.device_put`` on a background
thread so the training thread only dequeues device-resident ``(K, B, ...)``
blocks: while the device crunches block *n*, the host stages block *n+1*
(double-buffered up to ``TrainConfig.prefetch_depth`` blocks in flight).
"""
from __future__ import annotations

import logging
import os
import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, TrainConfig
from repro.tracing import span

log = logging.getLogger(__name__)


class PrefetchStalled(RuntimeError):
    """The consumer waited longer than the stall timeout for the next block.

    Raised instead of blocking forever on a wedged worker (a hung filesystem,
    a deadlocked source).  The message carries the liveness diagnostics a
    post-mortem needs; the worker (if any) is left running — call ``close()``
    to tear it down."""


@dataclass
class SyntheticTask:
    vocab: int
    seq_len: int
    noise: float = 0.1
    seed: int = 0

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self.perm = rng.permutation(self.vocab)

    def sample(self, rng: np.random.Generator, batch: int) -> Dict[str, np.ndarray]:
        toks = np.empty((batch, self.seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, batch)
        flip = rng.random((batch, self.seq_len)) < self.noise
        rand = rng.integers(0, self.vocab, (batch, self.seq_len))
        for t in range(self.seq_len):
            nxt = self.perm[toks[:, t]]
            toks[:, t + 1] = np.where(flip[:, t], rand[:, t], nxt)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].astype(np.int32)}


def _step_rng(seed: int, seed_offset: int, step: int) -> np.random.Generator:
    """Per-step generator keyed by the absolute step index — resume-safe."""
    return np.random.default_rng((seed + 1 + seed_offset, step))


def make_batches(cfg: ModelConfig, tcfg: TrainConfig, *, steps: Optional[int] = None,
                 seed_offset: int = 0, noise: float = 0.1, start_step: int = 0
                 ) -> Iterator[Dict[str, np.ndarray]]:
    """Yield the batches for absolute steps ``start_step, start_step+1, ...``.

    ``steps`` bounds the count (default: ``tcfg.steps - start_step``).  Batch
    ``i`` depends only on ``(tcfg.seed, seed_offset, i)``, so a resumed run
    continues the stream instead of replaying it from batch 0.
    """
    task = SyntheticTask(cfg.vocab, tcfg.seq_len, noise=noise, seed=tcfg.seed)
    n = steps if steps is not None else max(tcfg.steps - start_step, 0)
    for step in range(start_step, start_step + n):
        rng = _step_rng(tcfg.seed, seed_offset, step)
        batch = task.sample(rng, tcfg.global_batch)
        if cfg.family == "encdec":
            batch["frames"] = rng.standard_normal(
                (tcfg.global_batch, cfg.n_frames, cfg.d_model), np.float32) * 0.02
        yield batch


def batch_specs(cfg: ModelConfig, batch: int, seq: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for one training batch (used by the dry-run)."""
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.family == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct((batch, cfg.n_frames, cfg.d_model),
                                               jnp.bfloat16)
    return specs


def stack_batches(batches: Sequence[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Stack K per-step batches into one ``(K, B, ...)`` block (host-side)."""
    assert batches, "cannot stack an empty block"
    return {k: np.stack([np.asarray(b[k]) for b in batches])
            for k in batches[0]}


class Prefetcher:
    """Background-thread batch-block pipeline (DESIGN.md §4).

    Pulls per-step batches from ``source``, groups them into blocks of the
    sizes given by ``sizes`` (the controller's block schedule: ``K, K, ...,
    tail``), stacks each block to ``(size, B, ...)`` and places it on device
    via ``place`` (default ``jax.device_put``; the trainer passes a mesh-aware
    placer built from the launch batch shardings).  Up to ``depth`` placed
    blocks are kept in flight, so the ``device_put`` of block *n+1* overlaps
    the device executing block *n*.

    ``depth <= 0`` degrades to fully synchronous block building on the calling
    thread (same results, no thread) — the deterministic-ordering debug mode.
    Iteration ends when ``sizes`` is exhausted or ``source`` runs dry; a
    source that dies mid-block yields the short remainder (every produced
    batch gets trained).  Worker exceptions re-raise on the consuming thread
    at the next ``next()``.

    Robustness (DESIGN.md §4): per-batch reads retry up to ``retries`` times
    on ``OSError`` with exponential backoff starting at ``retry_backoff``
    seconds — transient I/O blips never surface; a persistent failure
    re-raises the *original* exception on the consumer.  ``stall_timeout``
    (seconds; 0 disables) bounds how long ``next()`` waits on the worker
    before raising :class:`PrefetchStalled` instead of hanging forever.
    """

    def __init__(self, source: Iterator[Dict[str, np.ndarray]],
                 sizes: Sequence[int], *, depth: int = 2,
                 place: Optional[Callable] = None, retries: int = 3,
                 retry_backoff: float = 0.05, stall_timeout: float = 0.0):
        self._source = iter(source)
        self._sizes = list(sizes)
        self._place = place or jax.device_put
        self._sync = depth <= 0
        self._exhausted = False
        self._retries = max(int(retries), 0)
        self._retry_backoff = max(float(retry_backoff), 0.0)
        self._stall_timeout = max(float(stall_timeout), 0.0)
        self.leaked_thread = False
        if self._sync:
            self._pos = 0
            return
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="repro-prefetch")
        self._thread.start()

    def _next_batch(self) -> Dict[str, np.ndarray]:
        """One source read under the bounded-retry policy: transient
        ``OSError``s back off and retry; the budget exhausting re-raises the
        last error; a source that dies *because of* the error (StopIteration
        on the retry) re-raises the original error too — a dead reader must
        not masquerade as clean end-of-data."""
        err: Optional[OSError] = None
        delay = self._retry_backoff
        for attempt in range(self._retries + 1):
            try:
                return next(self._source)
            except StopIteration:
                if err is not None:
                    raise err
                raise
            except OSError as e:
                err = e
                if attempt >= self._retries:
                    raise
                log.warning("batch read failed (%s); retry %d/%d in %.3fs",
                            e, attempt + 1, self._retries, delay)
                with span("/repro/data/read_retry", attempt=attempt + 1):
                    if delay > 0:
                        time.sleep(delay)
                delay *= 2
        raise AssertionError("unreachable")

    def _build(self, size: int):
        with span("/repro/data/build", size=size):
            block: List[Dict[str, np.ndarray]] = []
            for _ in range(size):
                if not self._sync and self._stop.is_set():
                    return None  # close() mid-build: stop consuming the source
                try:
                    block.append(self._next_batch())
                except StopIteration:
                    break
            if not block:
                return None
            # A short final block (source ran dry mid-block) is yielded as-is
            # — every batch the source produced gets trained.
            block = stack_batches(block)
            with span("/repro/data/place"):
                return self._place(block)

    def _worker(self):
        try:
            for size in self._sizes:
                if self._stop.is_set():
                    return
                block = self._build(size)
                if block is None:
                    break
                while not self._stop.is_set():
                    try:
                        self._q.put(block, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surfaced on the consumer thread
            self._err = e
        finally:
            while not self._stop.is_set():
                try:
                    self._q.put(None, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        if self._sync:
            if self._pos >= len(self._sizes):
                self._exhausted = True
                raise StopIteration
            block = self._build(self._sizes[self._pos])
            if block is None:
                self._exhausted = True
                raise StopIteration
            self._pos += 1
            return block
        if self._stall_timeout > 0:
            try:
                item = self._q.get(timeout=self._stall_timeout)
            except queue.Empty:
                raise PrefetchStalled(
                    f"no block within {self._stall_timeout:.1f}s "
                    f"(worker alive={self._thread.is_alive()}, "
                    f"queue depth={self._q.qsize()}, "
                    f"pending error={self._err!r})") from None
        else:
            item = self._q.get()
        if item is None:
            self._exhausted = True
            self.close()
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        """Stop the worker and release queue slots (idempotent); further
        ``next()`` calls raise StopIteration instead of blocking."""
        if self._sync:
            return
        self._exhausted = True
        self._stop.set()
        while True:  # drain so a blocked put observes the stop flag
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            # A worker stuck in a batch read survives the join — it is a
            # daemon thread, so it cannot hang shutdown, but the leak must be
            # visible (it still holds the source and any mid-build blocks).
            self.leaked_thread = True
            log.warning("Prefetcher.close(): worker %s still alive after 5s "
                        "join; leaking daemon thread",
                        self._thread.name)


class PackedFileDataset:
    """Memory-mapped packed token file: shape (n_docs, seq+1) int32.

    Per-host sharding: host i of H reads rows i::H — no cross-host I/O.  Used by
    the end-to-end example; write files with :meth:`write`.
    """

    def __init__(self, path: str, seq_len: int, *, host_id: int = 0,
                 n_hosts: int = 1):
        self.arr = np.load(path, mmap_mode="r")
        assert self.arr.shape[1] == seq_len + 1, self.arr.shape
        self.rows = np.arange(host_id, self.arr.shape[0], n_hosts)
        self.seq_len = seq_len

    @staticmethod
    def write(path: str, tokens: np.ndarray):
        np.save(path, np.asarray(tokens, np.int32))

    def batches(self, batch: int, *, seed: int = 0, epochs: int = 1_000_000,
                start_step: int = 0) -> Iterator[Dict[str, np.ndarray]]:
        """Shuffled batches; the per-epoch permutation is keyed by ``(seed,
        epoch)`` so ``start_step`` (an absolute batch index) seeks in O(1) —
        a resumed run continues the stream instead of replaying batch 0."""
        per_epoch = max((len(self.rows) - batch) // batch + 1, 0) \
            if len(self.rows) >= batch else 0
        if per_epoch == 0:
            return
        first_epoch, offset = divmod(start_step, per_epoch)
        for epoch in range(first_epoch, epochs):
            order = np.random.default_rng((seed, epoch)).permutation(self.rows)
            for i in range(offset * batch, len(order) - batch + 1, batch):
                rows = np.sort(order[i:i + batch])
                chunk = self.arr[rows]
                yield {"tokens": chunk[:, :-1].astype(np.int32),
                       "labels": chunk[:, 1:].astype(np.int32)}
            offset = 0
