"""Spans and live counters of the continuous-batching engine.

The engine always keeps a :class:`LiveCounters` for the drain in progress
(``ContinuousBatchingEngine.live_counters``), updated where the work
happens; ``snapshot()`` at two instants gives the counts of the interval
between them, and ``ServeSummary.counters`` is built from the same fields
when the drain ends.

A :class:`Telemetry` passed to the engine (``telemetry=``; off by default)
also records the drain's spans in memory: name, start and end on
``time.perf_counter``, the id of the span it opened inside (0 at the top of
a thread), the thread, and a few attributes. Each span is opened as a
``jax.profiler.TraceAnnotation`` of the same name, with its integer and
string attributes as the annotation's arguments, so a profiler trace shows
the spans on the host threads. A recorded span and its annotation open and
close together, so the ``serve.tick`` twins give the offset between the two
clocks.

Spans (thread; attributes):

* ``serve.tick`` (producer; ``tick``): one iteration of the drain loop.
* ``serve.admit`` (producer; ``admitted``): admission of waiting requests.
* ``serve.prepare`` (producer): host-side step inputs: prefill rows and page
  allocation, decode positions and the block-table copy, prefix hashing of
  filled blocks.
* ``serve.step_call`` (producer; ``kind``, ``seq``, ``bucket``, ``tokens``,
  ``rows``; in memory only: ``start`` and ``valid`` per prefill row, ``pos``
  of the live decode rows): the call into a compiled step and the token
  pick on its logits.
* ``serve.emit_wait`` (producer; ``seq``): handing a step's tokens to
  delivery: the queue put (async) or the blocking readback (sync).

The delivery thread records no spans: its readbacks are counted
(``n_readbacks``, ``steps_read_back``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time

import jax

__all__ = ["LiveCounters", "Span", "Telemetry", "NOSPAN"]

# entered where the engine has no recorder: no span, no annotation
NOSPAN = contextlib.nullcontext()


@dataclasses.dataclass
class LiveCounters:
    """Counts of one drain so far. ``emit_wait_s`` is the time in
    ``serve.emit_wait`` spans, measured whether spans are kept or not.
    Prefix-cache hits and preemptions are counted where they happen, by the
    drain's pool and scheduler; ``snapshot()`` reads them there."""
    pool: object = dataclasses.field(repr=False)
    sched: object = dataclasses.field(repr=False)
    prefill_chunks: int = 0
    prefill_tokens: int = 0
    decode_steps: int = 0
    decode_live_token_steps: int = 0
    decode_capacity_token_steps: int = 0
    # steps of one layer's fused paged-decode kernel call, and those that
    # copy a live page (``kernels.paged_attention.kernel_steps``)
    decode_kernel_grid_steps: int = 0
    decode_kernel_copy_steps: int = 0
    n_readbacks: int = 0
    steps_read_back: int = 0
    emit_wait_s: float = 0.0
    decode_s: float = 0.0         # each decode step, dispatch to delivered
    drain_wait_s: float = 0.0     # the final wait for delivery to empty

    def snapshot(self) -> dict:
        """A copy of every count."""
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self)[2:]}
        # a dense pool has no prefix cache
        out["prefix_hit_tokens"] = getattr(self.pool, "prefix_hit_tokens", 0)
        out["preemptions"] = self.sched.preemptions
        return out


class Span:
    __slots__ = ("id", "name", "parent", "thread", "start", "end", "attrs",
                 "_annotation")

    def __init__(self, id_, name, parent, thread, start, attrs, annotation):
        self.id, self.name = id_, name
        self.parent, self.thread = parent, thread
        self.start, self.end, self.attrs = start, None, attrs
        self._annotation = annotation

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"{self.start:.6f}..{self.end})")


class Telemetry:
    """In-memory recorder of the engine's spans (see the module docstring).
    Producer and consumer threads record into the same list; each thread
    nests its own spans."""

    def __init__(self):
        self.spans: list = []
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def open(self, name: str, t: float = None, /, **attrs) -> Span:
        """Open a span at ``t`` (now when None) inside the innermost span
        open on this thread."""
        stack = self._stack()
        args = {k: v for k, v in attrs.items() if isinstance(v, (int, str))}
        annotation = jax.profiler.TraceAnnotation(name, **args)
        annotation.__enter__()
        if t is None:
            t = time.perf_counter()
        sp = Span(next(self._ids), name, stack[-1].id if stack else 0,
                  threading.current_thread().name, t, attrs, annotation)
        stack.append(sp)
        self.spans.append(sp)
        return sp

    def close(self, sp: Span, t: float = None) -> None:
        """Close ``sp`` at ``t`` (now when None)."""
        sp.end = time.perf_counter() if t is None else t
        sp._annotation.__exit__(None, None, None)
        sp._annotation = None
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:            # an error unwound past a child span
            stack.remove(sp)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        sp = self.open(name, **attrs)
        try:
            yield sp
        finally:
            self.close(sp)
