"""StreamSource: one iterator abstraction over every stream shape.

The trainers consume dict-of-arrays stacked over rounds (``lax.scan`` xs):
``{"tokens": (R, b, s), "labels": (R, b, s)}``. A ``StreamSource`` produces
exactly that, but decouples *where rounds come from* — a finite in-memory
array, a Python generator, or a live/unbounded feed — from the runners:

- ``ArrayStreamSource``    — finite dict-of-arrays (what ``make_stream``
  returns), with an exactly-once cursor and ``seek`` for resume.
- ``IterableStreamSource`` — any iterator/generator of per-round batch
  dicts ``{k: (b, ...)}``; may be unbounded (``length=None``).
- ``BufferedStreamSource`` — a replay-buffered, prefetching view over any
  source: the feeder of both incremental pipeline paths (the pipelined
  trainer and the elastic trainer). ``take`` retains what it hands out
  until ``ack()``; ``rewind()`` re-serves the un-acked rounds
  (exactly-once fault re-runs without ``seek``); ``prefetch(n)`` pulls the
  next rounds on a background thread while the consumer computes.
- ``LimitedStreamSource``  — at most ``max_rounds`` rounds of a source
  (how ``run(max_rounds=...)`` bounds an unbounded feed).
- ``as_stream_source``     — coercion: sources pass through, dicts wrap,
  ``StreamConfig`` synthesizes, iterables/generators wrap.

``take(n)`` pops up to ``n`` rounds (stacked); ``materialize(max_rounds)``
drains to one stacked dict — unbounded sources require ``max_rounds``.
"""

from __future__ import annotations

import collections
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Union

import numpy as np

from repro import faults as faults_lib
from repro.core.spans import span
from repro.faults import FeederDeathError, TransientFaultError
from repro.ocl.streams import StreamConfig, make_stream

Batch = Dict[str, np.ndarray]


def _concat_chunks(chunks: List[Batch]) -> Batch:
    """Stack a list of round-stacked chunk dicts into one (no copy for 1)."""
    if len(chunks) == 1:
        return chunks[0]
    return {k: np.concatenate([c[k] for c in chunks], axis=0) for k in chunks[0]}


class StreamSource:
    """Base protocol; subclasses implement ``take`` and ``length``."""

    @property
    def length(self) -> Optional[int]:
        """Total rounds, or ``None`` when unbounded/unknown."""
        raise NotImplementedError

    @property
    def remaining(self) -> Optional[int]:
        """Rounds not yet consumed, or ``None`` when unbounded/unknown."""
        raise NotImplementedError

    def take(self, n: int) -> Optional[Batch]:
        """Pop up to ``n`` rounds stacked as ``{k: (m, b, ...)}``, m ≤ n.

        Returns ``None`` once the source is exhausted. Consumption is
        exactly-once: rounds returned here are never returned again.
        """
        raise NotImplementedError

    def materialize(self, max_rounds: Optional[int] = None) -> Batch:
        """Drain (up to ``max_rounds``) into one stacked dict-of-arrays."""
        if max_rounds is None and self.length is None:
            raise ValueError(
                "unbounded StreamSource: pass max_rounds (e.g. "
                "session.run(max_rounds=...)) to bound the run"
            )
        chunks = []
        left = max_rounds if max_rounds is not None else self.remaining
        while left is None or left > 0:
            got = self.take(min(left or 256, 256))
            if got is None:
                break
            chunks.append(got)
            if left is not None:
                left -= next(iter(got.values())).shape[0]
        if not chunks:
            raise ValueError("StreamSource is exhausted — nothing to run")
        keys = chunks[0].keys()
        return {k: np.concatenate([c[k] for c in chunks], axis=0) for k in keys}

    def __iter__(self) -> Iterator[Batch]:
        while True:
            got = self.take(1)
            if got is None:
                return
            yield {k: v[0] for k, v in got.items()}


class ArrayStreamSource(StreamSource):
    """Finite stream backed by stacked arrays, with a consumption cursor."""

    def __init__(self, arrays: Batch):
        if not arrays:
            raise ValueError("empty stream dict")
        lens = {k: v.shape[0] for k, v in arrays.items()}
        if len(set(lens.values())) != 1:
            raise ValueError(f"inconsistent round counts across fields: {lens}")
        self.arrays = {k: np.asarray(v) for k, v in arrays.items()}
        self._length = next(iter(lens.values()))
        self.cursor = 0

    @property
    def length(self) -> Optional[int]:
        return self._length

    @property
    def remaining(self) -> Optional[int]:
        return self._length - self.cursor

    def seek(self, round_idx: int) -> None:
        """Move the cursor (checkpoint resume: skip already-consumed rounds)."""
        if not 0 <= round_idx <= self._length:
            raise ValueError(f"seek({round_idx}) outside [0, {self._length}]")
        self.cursor = round_idx

    def take(self, n: int) -> Optional[Batch]:
        if self.cursor >= self._length:
            return None
        end = min(self.cursor + n, self._length)
        out = {k: v[self.cursor:end] for k, v in self.arrays.items()}
        self.cursor = end
        return out


class IterableStreamSource(StreamSource):
    """Wraps an iterator of per-round batch dicts; may be unbounded."""

    def __init__(self, rounds: Iterable[Batch], length: Optional[int] = None):
        self._it = iter(rounds)
        self._declared = length
        self._consumed = 0
        self._done = False

    @property
    def length(self) -> Optional[int]:
        return self._declared

    @property
    def remaining(self) -> Optional[int]:
        if self._done:
            return 0
        if self._declared is None:
            return None
        return self._declared - self._consumed

    def take(self, n: int) -> Optional[Batch]:
        rows = []
        for _ in range(n):
            try:
                rows.append(next(self._it))
            except StopIteration:
                self._done = True
                break
        if not rows:
            return None
        keys = set(rows[0])
        for i, r in enumerate(rows[1:], 1):
            if set(r) != keys:
                # never silently drop (or KeyError on) fields that drift
                # between rounds — a live feed producing ragged dicts is a
                # producer bug, and the stacked batch must stay rectangular
                raise ValueError(
                    "inconsistent stream fields at round "
                    f"{self._consumed + i}: {sorted(r)} != {sorted(keys)}"
                )
        self._consumed += len(rows)
        return {k: np.stack([np.asarray(r[k]) for r in rows]) for k in rows[0]}


class LimitedStreamSource(StreamSource):
    """At most ``max_rounds`` rounds of ``source``, then exhausted.

    Bounds an unbounded feed for one run (``session.run(max_rounds=...)``).
    ``length`` reports the cap for an unbounded inner source — the inner
    feed may still end earlier, in which case this source ends with it.
    """

    def __init__(self, source: StreamSource, max_rounds: int):
        if max_rounds < 0:
            raise ValueError(f"max_rounds must be >= 0, got {max_rounds}")
        self.source = source
        self.max_rounds = int(max_rounds)
        self._given = 0

    @property
    def length(self) -> Optional[int]:
        inner = self.source.length
        return self.max_rounds if inner is None else min(inner, self.max_rounds)

    @property
    def remaining(self) -> Optional[int]:
        left = self.max_rounds - self._given
        inner = self.source.remaining
        return left if inner is None else min(inner, left)

    def take(self, n: int) -> Optional[Batch]:
        n = min(n, self.max_rounds - self._given)
        if n <= 0:
            return None
        got = self.source.take(n)
        if got is not None:
            self._given += next(iter(got.values())).shape[0]
        return got


class BufferedStreamSource(StreamSource):
    """Replay-buffered, prefetching view over any ``StreamSource``.

    The feeder of the incremental pipeline paths (``core.ferret``'s
    pipelined trainer and ``runtime.elastic_trainer``). Three jobs:

    - **exactly-once under faults**: every round handed out by ``take`` is
      retained until ``ack()``; ``rewind()`` puts the un-acked rounds back
      at the front, so a failed segment re-runs on identical data without
      ``seek`` — unbounded live feeds included.
    - **prefetch**: ``prefetch(n)`` pulls the next ``n`` rounds from the
      inner source on a background thread, overlapping stream arrival
      with the consumer's compute. Prefetched rounds land in the pending
      buffer; nothing is lost if the consumer stops early.
    - **one-shot transform**: ``transform`` (e.g. an OCL algorithm's
      ``prepare_stream``) is applied to each pulled chunk exactly once, in
      stream order, before retention — a rewound segment replays the
      *prepared* rows instead of re-running a stateful preparation.

    Peak host residency is ``peak_buffered_rounds`` — O(segment + prefetch
    window), never O(stream). ``take_wait_s`` accumulates time spent
    blocked on the inner source (the un-overlapped arrival cost).

    ``retain=False`` turns the replay buffer off: ``take`` hands rounds
    out without keeping a copy (``rewind`` becomes a no-op). Use it for
    pass-through views that only exist to ``peek``/share a source — e.g.
    the session's shape-inference probe and its cross-run live-stream
    view — where the *consuming* trainer wraps this view in its own
    retaining feeder; stacking two retaining views would hold every round
    pulled through the inner one for the whole run, O(R) host memory.
    """

    def __init__(
        self,
        source: StreamSource,
        transform: Optional[Callable[[Batch], Batch]] = None,
        prefetch: bool = True,
        retain: bool = True,
    ):
        self.source = source
        self.transform = transform
        self.prefetch_enabled = prefetch
        self.retain = retain
        self._pending: collections.deque = collections.deque()  # transformed
        self._inflight: List[Batch] = []  # handed out, not yet acked
        self._exhausted = False
        self._future = None
        self._pool: Optional[ThreadPoolExecutor] = None
        self.peak_buffered_rounds = 0
        self.take_wait_s = 0.0

    @staticmethod
    def _nrounds(chunk: Batch) -> int:
        return next(iter(chunk.values())).shape[0]

    def _pending_rounds(self) -> int:
        return sum(self._nrounds(c) for c in self._pending)

    def pending_round_count(self) -> int:
        """Rounds pulled from the inner source but not yet handed out.

        A cheap, non-blocking observation (an in-flight prefetch is *not*
        synced): schedulers use it to size the next segment to what is
        physically available instead of blocking a shared serve loop."""
        return self._pending_rounds()

    def _note_peak(self) -> None:
        n = self._pending_rounds() + sum(self._nrounds(c) for c in self._inflight)
        self.peak_buffered_rounds = max(self.peak_buffered_rounds, n)

    def _admit(self, chunk: Optional[Batch]) -> None:
        """Transform-once and retain a chunk pulled from the inner source."""
        if chunk is None:
            self._exhausted = True
            return
        if self.transform is not None:
            with span("ferret.feeder.prepare"):
                chunk = self.transform(chunk)
        self._pending.append(chunk)
        self._note_peak()

    def _inner_take(self, n: int) -> Optional[Batch]:
        """``source.take`` with the ``stream.take`` injection point.

        A ``stall`` fault sleeps (a slow feed — observable in
        ``take_wait_s``, bit-exact otherwise); an ``error`` fault raises
        ``TransientFaultError`` *before* touching the source, so a retry
        consumes nothing twice.
        """
        spec = faults_lib.fire("stream.take", n=n)
        if spec is not None:
            if spec.kind == "stall":
                time.sleep(spec.arg)
                faults_lib.resolved("stream.take")
            elif spec.kind == "error":
                raise TransientFaultError("injected stream.take error")
        return self.source.take(n)

    def _prefetch_take(self, n: int) -> Optional[Batch]:
        """The background worker's take, with the feeder-death point."""
        with span("ferret.feeder.prepare"):
            spec = faults_lib.fire("stream.prefetch", n=n)
            if spec is not None and spec.kind == "feeder_death":
                raise FeederDeathError("injected prefetch feeder death")
            return self._inner_take(n)

    def _sync(self) -> None:
        if self._future is not None:
            (fut, n), self._future = self._future, None
            failed = None
            with span("ferret.feeder.wait") as wait:
                try:
                    got = fut.result()
                except FeederDeathError:
                    # the feeder thread died before touching the source:
                    # exactly-once holds because the failed take consumed
                    # nothing
                    failed = "stream.prefetch"
                except TransientFaultError:
                    # the worker's *take* failed (transient,
                    # pre-consumption): the outstanding fault is at the
                    # take point, not the prefetch point
                    failed = "stream.take"
            self.take_wait_s += wait.seconds
            if failed is not None:
                # fall back to a synchronous pull of the same request
                self._pull(n)
                faults_lib.resolved(failed)
                return
            self._admit(got)

    def _pull(self, n: int) -> None:
        if self._exhausted:
            return
        with span("ferret.feeder.wait") as wait:
            try:
                got = self._inner_take(n)
            except TransientFaultError:
                # transient by contract (raised before any consumption):
                # one immediate retry
                got = self._inner_take(n)
                faults_lib.resolved("stream.take")
        self.take_wait_s += wait.seconds
        self._admit(got)

    # -- prefetch ----------------------------------------------------------
    def prefetch(self, n: int) -> None:
        """Start pulling the next ``n`` rounds on a background thread.

        No-op while a prefetch is already in flight, after exhaustion, or
        when prefetching is disabled. The inner source is only ever touched
        by one thread at a time: the worker owns it until the next
        main-thread operation syncs on the future.
        """
        if (
            not self.prefetch_enabled
            or n <= 0
            or self._exhausted
            or self._future is not None
        ):
            return
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="stream-prefetch"
            )
        # the request size rides with the future so a dead feeder can be
        # recovered by a synchronous pull of the same n (see _sync)
        self._future = (self._pool.submit(self._prefetch_take, n), n)

    def close(self) -> None:
        """Drain any in-flight prefetch and stop the worker thread.

        Exception-safe: consumers call this from a ``finally`` while an
        error may already be unwinding, so a *failed* in-flight take is
        dropped here instead of raised — during normal operation the
        background exception re-raises, original traceback attached, at
        the next main-thread sync point (``take``/``peek``/``ack`` path),
        which is where the consumer can act on it. Without the shutdown a
        non-daemon worker blocked on a slow feed outlives the trainer.
        """
        entry, self._future = self._future, None
        if entry is not None:
            try:
                self._admit(entry[0].result())
            except Exception:
                # the consumer is already unwinding its own error; but
                # KeyboardInterrupt/SystemExit must still get through or
                # a hung feed makes the process unstoppable
                pass
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- StreamSource protocol --------------------------------------------
    @property
    def length(self) -> Optional[int]:
        return self.source.length

    @property
    def remaining(self) -> Optional[int]:
        inner = self.source.remaining
        if self._exhausted:
            inner = 0
        if inner is None:
            return None
        return inner + self._pending_rounds()

    def take(self, n: int) -> Optional[Batch]:
        self._sync()
        while self._pending_rounds() < n and not self._exhausted:
            self._pull(n - self._pending_rounds())
        if not self._pending:
            return None
        out: List[Batch] = []
        got = 0
        while self._pending and got < n:
            chunk = self._pending.popleft()
            r = self._nrounds(chunk)
            if got + r > n:
                keep = n - got
                self._pending.appendleft({k: v[keep:] for k, v in chunk.items()})
                chunk, r = {k: v[:keep] for k, v in chunk.items()}, keep
            out.append(chunk)
            got += r
        stacked = _concat_chunks(out)
        if self.retain:
            self._inflight.append(stacked)
        self._note_peak()
        return stacked

    def materialize(self, max_rounds: Optional[int] = None) -> Batch:
        out = super().materialize(max_rounds)
        self.ack()
        return out

    # -- exactly-once bookkeeping -----------------------------------------
    def ack(self) -> None:
        """Confirm every handed-out round as consumed (drop the replay copy)."""
        self._inflight.clear()

    def rewind(self) -> None:
        """Put all un-acked rounds back at the front for replay."""
        self._sync()
        for chunk in reversed(self._inflight):
            self._pending.appendleft(chunk)
        self._inflight.clear()

    def try_seek(self, round_idx: int) -> bool:
        """Seek the inner source (resume); discards all buffered rounds."""
        inner = self.source
        ok = (
            inner.try_seek(round_idx)
            if isinstance(inner, BufferedStreamSource)
            else getattr(inner, "seek", None) is not None
        )
        if not ok:
            return False
        self._sync()
        self._pending.clear()
        self._inflight.clear()
        self._exhausted = False
        if not isinstance(inner, BufferedStreamSource):
            inner.seek(round_idx)
        return True

    # -- buffered-tail access (elastic re-plan refresh) --------------------
    def peek(self, n: int = 1) -> Optional[Batch]:
        """The next ``n`` rounds without consuming them (pulled if needed)."""
        self._sync()
        while self._pending_rounds() < n and not self._exhausted:
            self._pull(n - self._pending_rounds())
        if not self._pending:
            return None
        rows: List[Batch] = []
        got = 0
        for chunk in self._pending:
            keep = min(n - got, self._nrounds(chunk))
            rows.append({k: v[:keep] for k, v in chunk.items()})
            got += keep
            if got >= n:
                break
        return _concat_chunks(rows)

    def buffered_rows(self) -> Optional[Batch]:
        """All pending (pulled, not yet handed out) rounds as one stacked
        dict — the physically-held piece of the stream tail an elastic
        re-plan may refresh in place. Requires no un-acked rounds."""
        self._sync()
        if self._inflight:
            raise RuntimeError(
                "buffered_rows with un-acked rounds in flight: ack() or "
                "rewind() first"
            )
        if not self._pending:
            return None
        return _concat_chunks(list(self._pending))

    def replace_buffered(self, rows: Batch) -> None:
        """Swap the pending rounds for refreshed ones (same round count)."""
        self._sync()
        have = self._pending_rounds()
        got = self._nrounds(rows)
        if got != have:
            raise ValueError(
                f"replace_buffered: {got} rounds given, {have} buffered"
            )
        self._pending.clear()
        self._pending.append(rows)


StreamLike = Union[StreamSource, Batch, StreamConfig, Iterable[Batch]]


def as_stream_source(obj: StreamLike, length: Optional[int] = None) -> StreamSource:
    """Coerce anything stream-shaped into a ``StreamSource``."""
    if isinstance(obj, StreamSource):
        return obj
    if isinstance(obj, StreamConfig):
        return ArrayStreamSource(make_stream(obj))
    if isinstance(obj, dict):
        return ArrayStreamSource(obj)
    if hasattr(obj, "__iter__") or hasattr(obj, "__next__"):
        return IterableStreamSource(obj, length=length)
    raise TypeError(
        f"cannot interpret {type(obj).__name__} as a stream: pass a "
        "StreamSource, a dict of (R, b, ...) arrays, a StreamConfig, or an "
        "iterable of per-round batch dicts"
    )


def coerce_trainer_stream(stream: StreamLike, caller: str) -> StreamSource:
    """The trainers' single stream-coercion entry point.

    ``StreamSource`` objects pass straight through. Anything else — in
    particular the historical raw dict-of-arrays form — is coerced via
    ``as_stream_source`` with a ``DeprecationWarning``: the trainer-level
    compat wrapping used to be copy-pasted per trainer, and the session
    layer (``FerretSession(stream=...)``) is the supported place to hand
    over raw arrays.
    """
    if isinstance(stream, StreamSource):
        return stream
    warnings.warn(
        f"passing a raw {type(stream).__name__} stream to {caller} is "
        "deprecated: wrap it with repro.api.as_stream_source(...) or use "
        "FerretSession(stream=...), which accepts raw arrays directly",
        DeprecationWarning,
        stacklevel=3,
    )
    return as_stream_source(stream)
