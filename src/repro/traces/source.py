"""Bounded rolling-window trace sources for streaming replay.

``stream_replay`` consumes a ``TraceSource``: per-core request streams with
*bounded random access* — each replay step stages a fixed-shape buffer of the
next ``chunk_len`` requests **per core**, starting at each core's own global
position (cores drain their streams at different rates, so the staging
window is ragged across cores). The source keeps only the columns between
the slowest core's position and the fastest core's position plus one stage
resident — memory is ``O(core spread + chunk_len)`` columns, independent of
total trace length.

Chunks are ingested lazily from an iterator with a double-buffered
background prefetch thread (the ``repro.data.pipeline.Prefetcher`` idiom):
the host half of the next chunk — file parsing, decompression, trace
synthesis — overlaps the device's replay of the current one. The *staging*
buffer itself cannot be prefetched exactly (its start positions depend on
how many requests the device consumed, which is only known after the step
returns), so the overlap lives at the ingestion layer where all the host
cost is.
"""
from __future__ import annotations

import queue
import threading
import time
import types
from typing import Iterable, Iterator, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.core.system import Trace

INT32_MAX = np.iinfo(np.int32).max


def _pull_retry(it: Iterator[Trace], retries: int,
                backoff: float) -> Optional[Trace]:
    """``next(it, None)`` with bounded retry on transient read errors.

    A flaky source (NFS hiccup, racing writer, transient decode failure)
    gets ``retries`` extra attempts with exponential backoff before the
    exception propagates. Only ``Exception`` retries — ``KeyboardInterrupt``
    and friends surface immediately — and generators are excluded by
    construction (a generator is dead after raising; retrying ``next()`` on
    one just yields ``StopIteration``, which would silently truncate the
    stream instead of failing it). The attempt budget is per pull, so a
    source that recovers resets its budget for the next chunk.
    """
    delay = backoff
    for attempt in range(retries + 1):
        try:
            return next(it, None)
        except Exception:
            if attempt == retries or isinstance(it, types.GeneratorType):
                raise
            time.sleep(delay)
            delay *= 2
    raise AssertionError("unreachable")


class _ChunkPrefetcher:
    """Pull Trace chunks from an iterator on a background thread (depth 2).

    An exception inside the iterator (parse error, I/O failure) is captured
    and re-raised from ``next()`` on the consumer thread — a failed ingest
    must fail the replay, not masquerade as a short stream. Transient
    errors optionally retry with bounded exponential backoff
    (``retries``/``backoff``) before the relay fires."""

    _SENTINEL = object()

    def __init__(self, it: Iterator[Trace], depth: int = 2,
                 retries: int = 0, backoff: float = 0.05):
        self._q: "queue.Queue" = queue.Queue(depth)
        self._err: Optional[BaseException] = None
        self._retries = int(retries)
        self._backoff = float(backoff)
        self._thread = threading.Thread(
            target=self._worker, args=(it,), daemon=True)
        self._thread.start()

    def _worker(self, it: Iterator[Trace]):
        try:
            while True:
                chunk = _pull_retry(it, self._retries, self._backoff)
                if chunk is None:
                    break
                self._q.put(chunk)
        except BaseException as e:              # noqa: BLE001 — relayed
            self._err = e
        finally:
            self._q.put(self._SENTINEL)

    def next(self) -> Optional[Trace]:
        got = self._q.get()
        if got is self._SENTINEL and self._err is not None:
            raise self._err
        return None if got is self._SENTINEL else got


class TraceSource:
    """Rolling window over per-core request streams.

    Build with :meth:`from_trace` (in-memory, total length known up front)
    or :meth:`from_chunks` (lazy iterator of ``Trace`` chunks concatenated
    along the time axis; the total length is discovered when the iterator
    ends). All chunks must share ``n_cores``.
    """

    def __init__(self, chunks: Iterator[Trace], n_cores: Optional[int] = None,
                 prefetch: bool = True, retries: int = 0,
                 backoff: float = 0.05):
        self._fetch: Union[_ChunkPrefetcher, Iterator[Trace], None]
        it = iter(chunks)
        self._retries = int(retries)
        self._backoff = float(backoff)
        self._fetch = (_ChunkPrefetcher(it, retries=self._retries,
                                        backoff=self._backoff)
                       if prefetch else it)
        self.n_cores = n_cores
        self._buf: Optional[list] = None   # list of 5 (n_cores, W) np arrays
        self.base = 0                      # global index of buffer column 0
        self.total: Optional[int] = None   # per-core length once discovered

    # ----------------------------------------------------------- constructors
    @classmethod
    def from_trace(cls, trace: Trace) -> "TraceSource":
        src = cls(iter(()), prefetch=False)
        src._append(trace)
        src._fetch = None
        src.total = src._buffered_end()
        return src

    @classmethod
    def from_chunks(cls, chunks: Iterable[Trace], prefetch: bool = True,
                    retries: int = 0, backoff: float = 0.05) -> "TraceSource":
        """Lazy source over an iterator of ``Trace`` chunks.

        ``retries``/``backoff`` give each chunk pull a bounded
        exponential-backoff retry budget against transient read errors
        (see ``_pull_retry``); the default keeps the historical
        fail-on-first-error behavior."""
        return cls(iter(chunks), prefetch=prefetch, retries=retries,
                   backoff=backoff)

    # -------------------------------------------------------------- ingestion
    def _append(self, chunk: Trace):
        arrs = [np.asarray(x) for x in chunk]
        if self.n_cores is None:
            self.n_cores = arrs[0].shape[0]
        if arrs[0].shape[0] != self.n_cores:
            raise ValueError(
                f"chunk has {arrs[0].shape[0]} cores, stream has {self.n_cores}")
        if self._buf is None:
            self._buf = arrs
        else:
            self._buf = [np.concatenate([a, b], axis=1)
                         for a, b in zip(self._buf, arrs)]

    def _buffered_end(self) -> int:
        return self.base + (self._buf[0].shape[1] if self._buf is not None else 0)

    def _pull_one(self) -> bool:
        if self._fetch is None:
            return False
        chunk = (self._fetch.next() if isinstance(self._fetch, _ChunkPrefetcher)
                 else _pull_retry(self._fetch, self._retries, self._backoff))
        if chunk is None:
            self._fetch = None
            self.total = self._buffered_end()
            return False
        self._append(chunk)
        return True

    def _fill_to(self, upto: int):
        while self._buffered_end() < upto and self._pull_one():
            pass

    def _trim(self, min_pos: int):
        drop = min_pos - self.base
        if drop > 0 and self._buf is not None:
            self._buf = [a[:, drop:] for a in self._buf]
            self.base = min_pos

    # ---------------------------------------------------------------- staging
    def stage(self, positions: np.ndarray,
              chunk_len: int) -> Tuple[Trace, jnp.ndarray]:
        """Fixed-shape staging buffer for the next replay step.

        Returns ``(chunk, stream_end)``: ``chunk`` holds, for each core,
        its ``chunk_len`` requests starting at ``positions[core]`` (entries
        past the stream end are invalid idle cells that the replay never
        reaches — ``stream_end`` stops the pointer first); ``stream_end[c]``
        is the count of real staged requests when core ``c``'s stream ends
        inside this buffer, else INT32_MAX ("more data behind the buffer").
        """
        positions = np.asarray(positions, np.int64)
        # one column past the furthest stage: a stream that ends exactly at
        # a stage's last column must be told so (stream_end), not left
        # waiting for data a lazy iterator has not yet said it lacks
        self._fill_to(int(positions.max()) + chunk_len + 1)
        self._trim(int(positions.min()))
        if self._buf is None:                       # empty stream
            if self.n_cores is None:
                raise ValueError("empty chunk stream with unknown n_cores")
            self._buf = [np.zeros((self.n_cores, 0), d) for d in
                         (np.int32, np.int32, bool, np.int32, bool)]
        width = self._buf[0].shape[1]
        idx = positions[:, None] + np.arange(chunk_len) - self.base
        inb = idx < width
        take = np.minimum(np.maximum(idx, 0), max(width - 1, 0))
        out = [np.take_along_axis(a, take, axis=1) if width else
               np.zeros((self.n_cores, chunk_len), a.dtype) for a in self._buf]
        out[4] = out[4] & inb                       # valid &= in-buffer
        if self.total is None:
            stream_end = np.full((self.n_cores,), INT32_MAX, np.int32)
        else:
            remaining = self.total - positions
            stream_end = np.where(remaining <= chunk_len, remaining,
                                  INT32_MAX).astype(np.int32)
        chunk = Trace(*(jnp.asarray(a) for a in out))
        return chunk, jnp.asarray(stream_end)

    def exhausted(self, positions: np.ndarray) -> bool:
        """True once every core's position has passed the stream end."""
        return (self.total is not None
                and bool((np.asarray(positions) >= self.total).all()))


def as_source(source) -> TraceSource:
    """Coerce a Trace, an iterable of Trace chunks, or a TraceSource."""
    if isinstance(source, TraceSource):
        return source
    if isinstance(source, Trace):
        return TraceSource.from_trace(source)
    return TraceSource.from_chunks(source)


def chunk_iter(trace: Trace, chunk_len: int) -> Iterator[Trace]:
    """Slice an in-memory trace into time-axis chunks (testing/benching)."""
    arrs = [np.asarray(x) for x in trace]
    T = arrs[0].shape[1]
    for off in range(0, T, chunk_len):
        yield Trace(*(jnp.asarray(a[:, off:off + chunk_len]) for a in arrs))
