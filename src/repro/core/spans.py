"""Host spans on the profiler's clock.

``span(name)`` times a block with ``time.perf_counter`` and, while
``jax.profiler`` traces, records it as a host event of that name
(``jax.profiler.TraceAnnotation``; with ``step=`` a
``StepTraceAnnotation``) on the clock of the device's ops. A counter read
from ``span.seconds`` and the event in the trace then time one interval.
Outside a trace the annotation costs a flag check.

Names, as the trainers' segment loops and the feeder open them:

- ``ferret.segment`` (step = the segment's index), holding, in order,
  ``ferret.replan``, ``ferret.remap``, ``ferret.refresh`` (elastic, at a
  budget switch), ``ferret.take``, ``ferret.schedule``, ``ferret.upload``,
  ``ferret.dispatch`` and ``ferret.fetch`` (the host blocked on the
  segment's per-round outputs, i.e. on the device);
- ``ferret.feeder.wait``: the consumer blocked on the stream source
  (``BufferedStreamSource.take_wait_s``); ``ferret.feeder.prepare``: the
  prefetch worker's take and the rows' one-shot transform.

The device side is named by ``jax.named_scope`` in the engine round
(``core/pipeline.py``: ``ferret.forward``, ``ferret.penalty``,
``ferret.push``, ``ferret.delta_gather``, ``ferret.compensate``,
``ferret.optimizer``, ``ferret.delta_ring``).
"""

from __future__ import annotations

import time
from typing import Optional

import jax


class span:
    """``with span("ferret.take") as s: ...``; then ``s.seconds``, and
    ``s.start`` / ``s.end`` on the ``time.perf_counter`` clock."""

    __slots__ = ("start", "end", "seconds", "_annotation")

    def __init__(self, name: str, step: Optional[int] = None):
        self.start = self.end = self.seconds = 0.0
        self._annotation = (
            jax.profiler.TraceAnnotation(name) if step is None
            else jax.profiler.StepTraceAnnotation(name, step_num=int(step))
        )

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = time.perf_counter()
        self.seconds = self.end - self.start
        self._annotation.__exit__(*exc)
        return False
