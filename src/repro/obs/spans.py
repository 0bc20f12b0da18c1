"""Span tracing: timed, nested sections of the request and control paths.

A span times one named section (`classify`, `t1_match`, `merge`, `refit`,
`swap`, `append`, ...) on `time.perf_counter_ns()` (`t0_ns`/`t1_ns`,
`wall_ms`). Two timed waits split what the host did inside it:
`span.sync(x)` blocks on a JAX value so device work lands in `sync_ms`,
and `span.fetch(x)` copies a device array to the host into `fetch_ms` and
`fetch_bytes`. A root span (no parent) also records its thread's CPU time
(`cpu_ms`) and the thread's minor/major page faults and involuntary
context switches over it (`minflt`, `majflt`, `nivcsw`), all from one
`getrusage(RUSAGE_THREAD)` at each end, so wall time the thread spent
off the CPU is told apart from time it spent working.

Each span also enters `jax.profiler.TraceAnnotation("repro." + name)`
while a profiler trace records, so the trace shows the program's layers
on the device timeline (and no annotation is built while none records).
JAX's monitoring hook adds every backend compile (persistent-cache loads
included) as a finished `compile` span, child of the innermost open span,
ending when the hook fires.

Spans nest: the recorder keeps a stack per process, so a `serve` span
opened around a batch contains `classify`/`t1_match`/`merge` children with
parent ids and depths, making one served batch or one drift-triggered
refit a single readable trace. Finished spans land in a bounded `Ring` as
plain dicts (JSON-ready for the exporter).

When the plane is disabled `repro.obs.span()` hands out the shared
`NULL_SPAN` whose methods are all no-ops — the hot path never builds a
Span object at all.
"""
from __future__ import annotations

import resource
import time
from typing import Callable, Iterator

import numpy as np
from jax.profiler import TraceAnnotation

from repro.obs.ring import Ring

# holds a traced benchmark window of spans with room to spare (a 51 s
# serving window at 240 q/s is at most 12,240 serve calls of 4-5 spans)
DEFAULT_SPAN_CAPACITY = 1 << 17

# JAX times the persistent-cache lookup inside this event, so it fires
# once for every program compiled or loaded from the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_RUSAGE = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)


class _NullSpan:
    """Shared do-nothing span handed out while the plane is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self

    def sync(self, value):
        return value

    def fetch(self, value) -> np.ndarray:
        return np.asarray(value)


NULL_SPAN = _NullSpan()


class Span:
    """One timed section; append-on-exit into the recorder's ring."""

    __slots__ = ("recorder", "name", "id", "parent", "depth", "t0_s",
                 "t0_ns", "t1_ns", "wall_ms", "sync_ms", "fetch_ms",
                 "fetch_bytes", "attrs", "_annotation", "_ru0")

    def __init__(self, recorder: "SpanRecorder", name: str, attrs: dict):
        self.recorder = recorder
        self.name = name
        self.id = -1
        self.parent = -1
        self.depth = 0
        self.t0_s = 0.0
        self.t0_ns = self.t1_ns = 0
        self.wall_ms = 0.0
        self.sync_ms = 0.0
        self.fetch_ms = 0.0
        self.fetch_bytes = 0
        self.attrs = attrs
        self._annotation = self._ru0 = None

    def __enter__(self) -> "Span":
        # the clock first: the span starts as close to its caller's
        # entry as it can, and its own bookkeeping counts inside it
        self.t0_ns = time.perf_counter_ns()
        self.t0_s = time.time()
        self.recorder._open(self)
        if self.parent < 0:
            self._ru0 = resource.getrusage(_RUSAGE)
        if TraceAnnotation.is_enabled():
            self._annotation = TraceAnnotation("repro." + self.name)
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if self._ru0 is not None:
            ru, ru0 = resource.getrusage(_RUSAGE), self._ru0
            cpu_s = ru.ru_utime + ru.ru_stime - ru0.ru_utime - ru0.ru_stime
            self.attrs.update(cpu_ms=round(cpu_s * 1e3, 4),
                              minflt=ru.ru_minflt - ru0.ru_minflt,
                              majflt=ru.ru_majflt - ru0.ru_majflt,
                              nivcsw=ru.ru_nivcsw - ru0.ru_nivcsw)
        self.t1_ns = time.perf_counter_ns()
        self.wall_ms = (self.t1_ns - self.t0_ns) * 1e-6
        self.recorder._close(self)
        return False

    def set(self, **attrs) -> "Span":
        """Attach attributes (batch size, generation, words scanned...)."""
        self.attrs.update(attrs)
        return self

    def sync(self, value):
        """Block until `value` is device-ready, folding the wait into
        `sync_ms`; returns `value` so call sites stay expressions."""
        import jax
        t0 = time.perf_counter_ns()
        # a value holding no JAX array has nothing to wait on; a device
        # error raised while waiting propagates to the caller
        if any(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(value)):
            value = jax.block_until_ready(value)
        self.sync_ms += (time.perf_counter_ns() - t0) * 1e-6
        return value

    def fetch(self, value) -> np.ndarray:
        """Copy `value` to the host as a numpy array, folding the copy into
        `fetch_ms` and its size into `fetch_bytes`. Sync first: a fetch of
        an array still being computed also waits for the device."""
        t0 = time.perf_counter_ns()
        out = np.asarray(value)
        self.fetch_ms += (time.perf_counter_ns() - t0) * 1e-6
        self.fetch_bytes += out.nbytes
        return out

    def to_dict(self) -> dict:
        d = {
            "id": self.id,
            "parent": self.parent,
            "name": self.name,
            "depth": self.depth,
            "t0_s": self.t0_s,
            "t0_ns": self.t0_ns,
            "t1_ns": self.t1_ns,
            "wall_ms": round(self.wall_ms, 4),
            "sync_ms": round(self.sync_ms, 4),
        }
        if self.fetch_bytes:
            d["fetch_ms"] = round(self.fetch_ms, 4)
            d["fetch_bytes"] = self.fetch_bytes
        if self.attrs:
            d.update(self.attrs)
        return d


class SpanRecorder:
    """Stack-nested span recorder over a bounded ring of finished spans.

    `seq` numbers every finished span monotonically (drops included), so
    the per-window exporter can cursor with `since(seq)` instead of
    re-reading the whole ring.
    """

    def __init__(self, capacity: int | None = DEFAULT_SPAN_CAPACITY):
        self.ring = Ring(capacity)
        self._stack: list[Span] = []
        self._next_id = 0

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def _open(self, span: Span) -> None:
        span.id = self._next_id
        self._next_id += 1
        if self._stack:
            span.parent = self._stack[-1].id
            span.depth = self._stack[-1].depth + 1
        self._stack.append(span)

    def record_compile(self, seconds: float) -> None:
        """Add a finished `compile` span of `seconds` ending now, as a
        child of the innermost open span."""
        t1 = time.perf_counter_ns()
        top = self._stack[-1] if self._stack else None
        self.ring.append({
            "id": self._next_id,
            "parent": top.id if top else -1,
            "name": "compile",
            "depth": top.depth + 1 if top else 0,
            "t0_s": time.time() - seconds,
            "t0_ns": t1 - int(seconds * 1e9),
            "t1_ns": t1,
            "wall_ms": round(seconds * 1e3, 4),
            "sync_ms": 0.0,
        })
        self._next_id += 1

    def listen_compiles(self, on: Callable[[], bool]) -> None:
        """Record JAX's backend compiles as `compile` spans while `on()`,
        through JAX's monitoring hook (register once per process)."""
        from jax import monitoring

        def on_duration(event: str, duration_secs: float, **_) -> None:
            if event == COMPILE_EVENT and on():
                self.record_compile(duration_secs)
        monitoring.register_event_duration_secs_listener(on_duration)

    def _close(self, span: Span) -> None:
        # tolerate out-of-order exits (exceptions unwound a child first)
        if span in self._stack:
            while self._stack and self._stack[-1] is not span:
                self._stack.pop()
            self._stack.pop()
        self.ring.append(span.to_dict())

    @property
    def seq(self) -> int:
        """Count of spans ever finished (drops included)."""
        return self.ring.n_seen

    def since(self, seq: int) -> list[dict]:
        """Finished spans with ordinal >= `seq` still retained in the ring."""
        start = self.ring.n_seen - len(self.ring)  # ordinal of ring[0]
        if seq <= start:
            return self.ring.to_list()
        if seq >= self.ring.n_seen:
            return []
        return self.ring[seq - start:]

    def to_list(self) -> list[dict]:
        return self.ring.to_list()

    def of_name(self, name: str) -> list[dict]:
        return [s for s in self.ring if s["name"] == name]

    def children(self, span_id: int) -> list[dict]:
        return [s for s in self.ring if s["parent"] == span_id]

    def walk(self) -> Iterator[dict]:
        return iter(self.ring)

    def reset(self) -> None:
        self.ring = Ring(self.ring.capacity)
        self._stack.clear()
        self._next_id = 0
