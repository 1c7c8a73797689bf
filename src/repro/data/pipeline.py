"""Sharded data pipeline with host-side prefetch.

Wraps a batch source (``SyntheticLM`` here; a real deployment would plug in
a file-backed loader with the same (step, shard)-pure interface) and
prefetches ahead of the training loop on a background thread. Because
batches are pure functions of (step, shard), skip-ahead after a
checkpoint-restore is O(1): just start asking for the restored step.

Host spans for ``jax.profiler``: ``repro.data.fetch`` around each batch
the prefetch thread makes, ``repro.data.get`` around each wait of the
consumer for one.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

import jax


class Prefetcher:
    def __init__(self, fetch: Callable[[int], Any], start_step: int,
                 depth: int = 2):
        self._fetch = fetch
        self._queue: "queue.Queue" = queue.Queue(maxsize=depth)
        self._next_step = start_step
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        step = self._next_step
        while not self._stop.is_set():
            try:
                with jax.profiler.TraceAnnotation("repro.data.fetch"):
                    item = (step, self._fetch(step))
            except Exception as e:  # surface loader errors to the consumer
                self._queue.put((step, e))
                return
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def get(self, expected_step: int) -> Any:
        with jax.profiler.TraceAnnotation("repro.data.get"):
            step, item = self._queue.get()
        if isinstance(item, Exception):
            raise item
        assert step == expected_step, (
            f"pipeline out of sync: got {step}, expected {expected_step}")
        return item

    def close(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2.0)


class DataPipeline:
    """Step-indexed, shard-aware pipeline with prefetch + skip-ahead."""

    def __init__(self, source, batch_size: int, seq_len: int,
                 shard: int = 0, num_shards: int = 1, prefetch: int = 2):
        self.source = source
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.shard = shard
        self.num_shards = num_shards
        self._prefetch_depth = prefetch
        self._prefetcher: Optional[Prefetcher] = None
        self._step = 0

    def _fetch(self, step: int):
        return self.source.batch(step, self.batch_size, self.seq_len,
                                 shard=self.shard,
                                 num_shards=self.num_shards)

    def start(self, step: int = 0):
        self.stop()
        self._step = step
        self._prefetcher = Prefetcher(self._fetch, step,
                                      depth=self._prefetch_depth)

    def next(self) -> Dict[str, Any]:
        if self._prefetcher is None:
            self.start(self._step)
        batch = self._prefetcher.get(self._step)
        self._step += 1
        return batch

    def next_at(self, step: int) -> Dict[str, Any]:
        """Fetch THE batch for ``step`` — the consumer's step index is
        authoritative, not the pipeline's internal cursor. When they
        agree this is ``next()``; when they don't (a supervisor replay
        after a restore the pipeline didn't hear about) the prefetcher
        restarts at ``step`` so the replayed step re-reads exactly the
        batch it saw the first time. Batches are pure in (step, shard),
        so a resync costs one prefetch restart, never wrong data."""
        if self._prefetcher is None or self._step != step:
            self.start(step)
        return self.next()

    def skip_to(self, step: int):
        """O(1) skip-ahead (restore-from-checkpoint path)."""
        self.start(step)

    def stop(self):
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
