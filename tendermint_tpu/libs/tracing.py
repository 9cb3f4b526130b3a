"""Span tracing for the verify pipeline: Chrome-trace export, per-stage
device timing, consensus step latency.

The reference ships opaque wall-clock numbers; here every hot stage of
the batch-verification pipeline (scheduler assembly, cache lookup, host
prep, table gather, device dispatch, readback, CPU fallback) and every
consensus step transition records a nestable span into a process-wide
``Tracer``. Completed spans land in a bounded ring buffer and export as
Chrome ``trace_events`` JSON, so a capture opens directly in
``chrome://tracing`` / https://ui.perfetto.dev.

Modes, driven by ``TENDERMINT_TPU_TRACE`` (or the ``[base] trace``
config knob / ``--trace`` CLI flag):

- ``off``  — spans are shared no-op objects; nothing is timed or stored
  (unless a metrics observer is bound, in which case spans are timed for
  the histograms but still not stored).
- ``ring`` — completed spans accumulate in the in-memory ring buffer,
  served at ``GET /debug/traces``.
- ``<path>`` — ring behavior plus a Chrome-trace JSON dump written to
  ``<path>`` at interpreter exit (and on explicit ``flush()``).

Span durations double as metric samples: a bound observer (see
``metrics_observer``) feeds spans tagged ``stage``+``engine`` into
``tendermint_ops_verify_stage_seconds`` and spans tagged ``step`` into
``tendermint_consensus_step_duration_seconds``, so the histograms and
the trace always agree — one clock, one count.

Nesting is per thread (a thread-local span stack); concurrency is safe
because each thread only touches its own stack and the ring append
takes the tracer lock.

Fleet scope (ISSUE 15): every recorded span carries a ``trace_id`` /
``span_id`` / ``parent_span_id``, and a compact :class:`TraceContext`
(17 bytes on the wire) rides verifyd frames, shm slab headers, and
JSON-RPC requests so a client's causal span and the server-side
scheduler/dispatch spans it provoked share one trace. ``attach()``
splices a remote parent into the local thread's span stack;
``current_context()`` reads the innermost active span for propagation.
``scripts/trace_merge.py`` fuses per-process exports (each export
records ``epoch_unix_us``, the wall-clock anchor of its perf-counter
epoch, for clock-skew correction).

What the host timeline would otherwise lack (ISSUE 24), all of it only
while the tracer records:

- per-lane steps as *phase totals*: ``sp.timed("sign_bytes", f)`` wraps
  a callable once outside a loop, and the span records
  ``sign_bytes_us`` / ``sign_bytes_n`` on completion — one event for
  10,000 lanes. On the no-op span ``timed`` returns ``f`` itself.
- ``gc_pause`` spans from ``gc.callbacks`` (args ``generation``,
  ``collected``), on the thread that collected.
- ``xla_compile`` spans from a ``jax.monitoring`` duration listener
  (arg ``event``; ``ts`` = now - duration), children of whatever span
  was open. Hooked only once ``jax`` is in ``sys.modules``: this module
  never imports it first. Both kinds only for intervals of
  ``EXTERNAL_SPAN_MIN_S`` or longer.
- every span also enters a ``jax.profiler.TraceAnnotation`` of its
  name, so a ``jax.profiler`` capture holds the program's spans on the
  trace's own clock beside "XLA Ops".
- CPU time beside wall time (ISSUE 34): a recorded span that opens
  with no span open under it on its thread (a call's outermost span,
  a worker thread's own) carries ``cpu_us``, its thread's CPU time
  between enter and exit (``time.thread_time_ns``): ``dur`` well above
  it, less what the thread waited for by design (the device, another
  thread's verdicts), is time it was descheduled, or waited for a lock
  or the GIL. A span that asked for it (``sp.process_cpu()``; the
  engine's ``verify_batch``) also carries ``proc_cpu_us``, the
  process's CPU time over all threads (``time.process_time_ns``):
  several times the span's ``dur`` means threads are spinning. Nested
  spans carry neither: where the machine with the chip runs, a read of
  either clock costs ~6 us (a read of ``perf_counter`` 0.08) and the
  clock moves in ticks of 10 ms, so thirty reads a 5 ms call would
  cost a tenth of the call and say nothing of any one span. Nor do
  spans somebody else timed (``gc_pause``, ``xla_compile``).
- one clock: ``tracer.epoch_ns`` is the ``perf_counter_ns`` of
  ``ts = 0`` (``otherData["epoch_perf_ns"]`` in an export), so a reader
  that timed a call with ``perf_counter_ns`` can place the call's spans
  without a probe span of its own.
"""

from __future__ import annotations

import atexit
import gc
import itertools
import json
import os
import struct
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Optional

TRACE_ENV = "TENDERMINT_TPU_TRACE"
CAP_ENV = "TENDERMINT_TPU_TRACE_CAP"
DEFAULT_CAP = 4096

OFF = "off"
RING = "ring"

# --- cross-process trace context ---------------------------------------------

_CTX_STRUCT = struct.Struct("<8s8sB")  # trace_id, span_id, flags
CTX_WIRE_LEN = _CTX_STRUCT.size  # 17 bytes

# Span IDs: a per-process random prefix + a monotonically increasing
# suffix. itertools.count is atomic under the GIL, so the hot path pays
# no lock and no urandom read per span.
_ID_PREFIX = os.urandom(4).hex()
_ID_COUNTER = itertools.count(1)


def _new_span_id() -> str:
    return "%s%08x" % (_ID_PREFIX, next(_ID_COUNTER) & 0xFFFFFFFF)


def _new_trace_id() -> str:
    return os.urandom(8).hex()


class TraceContext(NamedTuple):
    """Compact propagation context: 16-hex-char trace and span IDs plus
    a flags byte (bit 0 = sampled). ``to_bytes`` is the 17-byte wire
    form carried by verifyd frames and shm slab headers; ``to_header``
    is the string form for JSON-RPC request members."""

    trace_id: str
    span_id: str
    flags: int = 1

    def to_bytes(self) -> bytes:
        return _CTX_STRUCT.pack(
            bytes.fromhex(self.trace_id), bytes.fromhex(self.span_id), self.flags
        )

    @classmethod
    def from_bytes(cls, raw: bytes) -> Optional["TraceContext"]:
        if len(raw) != CTX_WIRE_LEN:
            return None
        tid, sid, flags = _CTX_STRUCT.unpack(raw)
        if tid == b"\x00" * 8:
            return None
        return cls(tid.hex(), sid.hex(), flags)

    def to_header(self) -> str:
        return "%s-%s-%02x" % (self.trace_id, self.span_id, self.flags)

    @classmethod
    def from_header(cls, header: Any) -> Optional["TraceContext"]:
        if not isinstance(header, str):
            return None
        parts = header.split("-")
        if len(parts) != 3 or len(parts[0]) != 16 or len(parts[1]) != 16:
            return None
        try:
            bytes.fromhex(parts[0])
            bytes.fromhex(parts[1])
            flags = int(parts[2], 16)
        except ValueError:
            return None
        return cls(parts[0], parts[1], flags)


class _RemoteAnchor:
    """A remote parent spliced into the thread's span stack by
    ``attach()``: children link under the caller's span_id without a
    local span event being recorded for the anchor itself."""

    __slots__ = ("name", "trace_id", "span_id")

    live = False  # an anchor, not a span: it takes no tags

    def __init__(self, ctx: TraceContext):
        self.name = "remote"
        self.trace_id = ctx.trace_id
        self.span_id = ctx.span_id


class _NopSpan:
    """Shared do-nothing span: the disabled tracer hands out this one
    instance, so `with tracer.span(...)` costs an attribute lookup and
    two no-op calls — no allocation, no clock reads."""

    __slots__ = ()
    live = False  # callers skip work done only to fill span arguments

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **tags: Any) -> None:
        pass

    def timed(self, phase: str, fn: Callable) -> Callable:
        return fn

    def process_cpu(self) -> None:
        pass


NOP_SPAN = _NopSpan()

# Intervals that somebody else timed (a collection, a jax trace or
# compile) leave a span only if they lasted this long. A young
# collection takes ~0.1 ms and a busy thread runs hundreds a second;
# tracing one kernel reports thousands of nested sub-millisecond
# traces. Either would push everything else out of the ring, and what
# stalls a call (a full collection, a retrace, a compile) is far above.
EXTERNAL_SPAN_MIN_S = 0.001

# jax.monitoring duration events recorded as ``xla_compile`` spans:
# tracing a jaxpr, lowering it, and the backend compile (or its load
# from the persistent cache).
_COMPILE_EVENT_PREFIX = "/jax/core/compile/"


class _Span:
    """One live span; a context manager recording on exit."""

    __slots__ = (
        "_tracer",
        "name",
        "args",
        "parent",
        "_t0",
        "trace_id",
        "span_id",
        "parent_span_id",
        "_remote",
        "_phases",
        "_annotation",
        "_cpu0",
        "_proc0",
    )
    live = True

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        args: Dict[str, Any],
        remote: Optional[TraceContext] = None,
    ):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.parent = ""
        self._t0 = 0.0
        self.trace_id = ""
        self.span_id = ""
        self.parent_span_id = ""
        self._remote = remote
        self._phases: Optional[Dict[str, Callable]] = None
        self._annotation: Any = None
        self._cpu0 = -1  # thread CPU ns at enter; -1: not read
        self._proc0 = -1  # process CPU ns; -1: not asked for

    def set(self, **tags: Any) -> None:
        """Attach tags discovered mid-span (hit counts, verdicts)."""
        self.args.update(tags)

    def timed(self, phase: str, fn: Callable) -> Callable:
        """``fn`` wrapped to add each call's time to this span's phase
        ``phase``: recorded on completion as ``<phase>_us`` and
        ``<phase>_n``. Wrap once, outside the loop that calls it, one
        callable a phase. The wrapper passes positional arguments only:
        it runs once a lane, and a quarter of a microsecond is what it
        may cost."""
        total = 0.0
        count = 0
        clock = time.perf_counter

        def timed_call(*args: Any) -> Any:
            nonlocal total, count
            t0 = clock()
            out = fn(*args)
            total += clock() - t0
            count += 1
            return out

        if self._phases is None:
            self._phases = {}
        self._phases[phase] = lambda: (total, count)
        return timed_call

    def process_cpu(self) -> None:
        """From here to the span's end, also count the process's CPU
        time over all threads: recorded as ``proc_cpu_us``. Called
        right after entering, by the one span a call that wants it."""
        if self._tracer._recording:
            self._proc0 = time.process_time_ns()

    def context(self) -> TraceContext:
        """Propagation context naming this span as the remote parent."""
        return TraceContext(self.trace_id, self.span_id, 1)

    def __enter__(self) -> "_Span":
        stack = self._tracer._stack()
        if self._remote is not None:
            # explicit remote parent beats local nesting: this span IS
            # the local continuation of the caller's cross-process span
            self.parent = "remote"
            self.trace_id = self._remote.trace_id
            self.parent_span_id = self._remote.span_id
        elif stack:
            top = stack[-1]
            self.parent = top.name
            self.trace_id = top.trace_id
            self.parent_span_id = top.span_id
        else:
            self.trace_id = _new_trace_id()
        self.span_id = _new_span_id()
        outermost = not (stack and stack[-1].live)
        stack.append(self)
        self._annotation = self._tracer._annotate(self.name)
        self._t0 = time.perf_counter()
        if outermost and self._tracer._recording:
            self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc: Any) -> bool:
        # the CPU clocks are read inside the wall interval on both sides
        if self._cpu0 >= 0:
            self.args["cpu_us"] = (time.thread_time_ns() - self._cpu0) / 1e3
        if self._proc0 >= 0:
            self.args["proc_cpu_us"] = (time.process_time_ns() - self._proc0) / 1e3
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        if self._phases:
            for phase, read in self._phases.items():
                seconds, count = read()
                if count:  # a phase this call never reached is left out
                    self.args[phase + "_us"] = round(seconds * 1e6, 3)
                    self.args[phase + "_n"] = count
        stack = self._tracer._stack()
        # Pop self specifically: a sibling span leaked across a generator
        # boundary must not tear another thread of the stack.
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self._tracer._complete(self, t1)
        return False


class Tracer:
    """Thread-safe span recorder with a bounded ring of completed spans."""

    def __init__(self, cap: int = DEFAULT_CAP):
        # re-entrant: a collection can start between two bytecodes of a
        # block that holds the lock, and its gc_pause lands in the ring
        # from the same thread
        self._lock = threading.RLock()
        self._tls = threading.local()
        self._ring: deque = deque(maxlen=cap)  # guarded-by: _lock
        # mode/path/recording/observer are written under _lock but read
        # racily on the hot path: a span started mid-configure() may land
        # in the old or new mode, which is fine for a tracer.
        self._mode = OFF  # guarded-by: none(racy hot-path read, see above)
        self._path: Optional[str] = None  # guarded-by: none(racy hot-path read)
        self._recording = False  # guarded-by: none(racy hot-path read)
        self._observer: Optional[Callable[[str, Dict[str, Any], float], None]] = None  # guarded-by: none(racy hot-path read)
        # flight-recorder sink: (kind, name, args, ts_s, dur_s) for every
        # completed span / instant, read racily like _observer
        self._flight: Optional[Callable[[str, str, Dict[str, Any], float, float], None]] = None  # guarded-by: none(racy hot-path read)
        # third sink slot: the kernel profiler (ops/introspect.py), fed
        # (name, args, seconds) like _observer, read racily like it
        self._profile: Optional[Callable[[str, Dict[str, Any], float], None]] = None  # guarded-by: none(racy hot-path read)
        # one reading for both forms: ``ts`` counts from ``_epoch``
        # (seconds), and ``epoch_ns`` says which perf_counter_ns that is
        self._epoch_ns = time.perf_counter_ns()
        self._epoch = self._epoch_ns / 1e9
        self._pid = os.getpid()
        self._thread_names: Dict[int, str] = {}  # guarded-by: _lock
        self._atexit_registered = False  # guarded-by: _lock
        self.recorded = 0  # guarded-by: _lock
        self.dropped = 0  # guarded-by: _lock
        # the hooks recording installs (and `off` removes): one bound
        # method each, so removal finds the object that was registered
        self._gc_hook = self._on_gc
        self._jax_hook = self._on_jax_duration
        self._jax_hooked = False  # guarded-by: _lock
        # jax.profiler.TraceAnnotation once jax is hooked, else None
        self._annotation_cls: Any = None  # guarded-by: none(racy hot-path read)

    # --- configuration -------------------------------------------------------

    def configure(self, mode: Optional[str] = None) -> "Tracer":
        """Set the mode: ``off`` | ``ring`` | a file path (ring + dump at
        exit). ``None``/empty reads ``TENDERMINT_TPU_TRACE``."""
        if not mode:
            mode = os.environ.get(TRACE_ENV, OFF) or OFF
        mode = mode.strip()
        cap = DEFAULT_CAP
        try:
            cap = max(1, int(os.environ.get(CAP_ENV, DEFAULT_CAP)))
        except ValueError:
            pass  # unparseable env override keeps the default cap
        with self._lock:
            self._mode = mode
            self._path = None if mode in (OFF, RING) else mode
            self._recording = mode != OFF
            if self._ring.maxlen != cap:
                self._ring = deque(self._ring, maxlen=cap)
            if self._path and not self._atexit_registered:
                self._atexit_registered = True
                atexit.register(self.flush)
            self._sync_hooks_locked()
        return self

    def _sync_hooks_locked(self) -> None:
        """Recording installs the collector callback and (if jax is
        already imported) the compile listener; ``off`` removes both,
        so a tracer that is off leaves nothing of its own behind."""
        if self._recording:
            if self._gc_hook not in gc.callbacks:
                gc.callbacks.append(self._gc_hook)
            self._hook_jax_locked()
            return
        if self._gc_hook in gc.callbacks:
            gc.callbacks.remove(self._gc_hook)
        if self._jax_hooked:
            sys.modules["jax"].monitoring.unregister_event_duration_listener(
                self._jax_hook
            )
            self._jax_hooked = False
            self._annotation_cls = None

    def _hook_jax_locked(self) -> None:
        """Never the first to import jax: a process that verifies on
        the host alone (or a tool that only merges traces) stays free
        of it, and one that imports it later is hooked by the first
        span after that."""
        if self._jax_hooked or "jax" not in sys.modules:
            return
        import jax.monitoring
        import jax.profiler

        jax.monitoring.register_event_duration_secs_listener(self._jax_hook)
        self._annotation_cls = jax.profiler.TraceAnnotation
        self._jax_hooked = True

    @property
    def mode(self) -> str:
        return self._mode

    @property
    def enabled(self) -> bool:
        return self._recording

    @property
    def epoch_ns(self) -> int:
        """``time.perf_counter_ns()`` of the instant every event's
        ``ts`` counts from."""
        return self._epoch_ns

    def set_metrics_observer(
        self, observer: Optional[Callable[[str, Dict[str, Any], float], None]]
    ) -> None:
        """Single observer slot (last binder wins, like
        device_policy.bind_metrics): called with (name, args, seconds)
        for every completed span, even in ``off`` mode, so metric
        histograms stay live when the ring is not kept."""
        with self._lock:
            self._observer = observer

    def set_flight_sink(
        self,
        sink: Optional[Callable[[str, str, Dict[str, Any], float, float], None]],
    ) -> None:
        """Single flight-recorder slot (libs/flightrec installs itself
        here): called with (kind, name, args, ts_seconds, dur_seconds)
        for every completed span and instant, even in ``off`` mode, so
        the post-mortem ring stays warm when the trace ring is not."""
        with self._lock:
            self._flight = sink

    def set_profile_sink(
        self, sink: Optional[Callable[[str, Dict[str, Any], float], None]]
    ) -> None:
        """Single profiler slot (ops/introspect installs itself here):
        called with (name, args, seconds) for every completed span, even
        in ``off`` mode, so the rolling kernel/compile digests stay live
        when the ring is not kept. None uninstalls (profiler off)."""
        with self._lock:
            self._profile = sink

    # --- recording -----------------------------------------------------------

    def _stack(self) -> List[Any]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(
        self,
        name: str,
        parent_ctx: Optional[TraceContext] = None,
        **args: Any,
    ) -> Any:
        """``with tracer.span("prep_chunk", lane_count=n):`` — nested
        spans inherit this one as parent (per-thread). ``parent_ctx``
        splices the span under a remote caller's context instead."""
        if (
            not self._recording
            and self._observer is None
            and self._flight is None
            and self._profile is None
        ):
            return NOP_SPAN
        return _Span(self, name, args, remote=parent_ctx)

    @contextmanager
    def attach(self, ctx: Optional[TraceContext]):
        """Make ``ctx`` the parent of every span this thread opens
        inside the block (no-op when ``ctx`` is None)."""
        if ctx is None or not self._recording:
            yield None
            return
        stack = self._stack()
        anchor = _RemoteAnchor(ctx)
        stack.append(anchor)
        try:
            yield anchor
        finally:
            if stack and stack[-1] is anchor:
                stack.pop()
            elif anchor in stack:
                stack.remove(anchor)

    def current_context(self) -> Optional[TraceContext]:
        """Context of this thread's innermost active span (None when no
        span is open or the tracer is not recording)."""
        if not self._recording:
            return None
        stack = self._stack()
        if not stack:
            return None
        top = stack[-1]
        if not top.trace_id:
            return None
        return TraceContext(top.trace_id, top.span_id, 1)

    def tag(self, **tags: Any) -> None:
        """Tags on this thread's innermost open span, from code that
        runs under it without holding it (no-op when none is open)."""
        stack = self._stack()
        if stack and stack[-1].live:
            stack[-1].set(**tags)

    def timed(self, phase: str, fn: Callable) -> Callable:
        """``fn`` timed as a phase of this thread's innermost open
        span, from code that runs under it without holding it; ``fn``
        itself when none is open."""
        stack = self._stack()
        if stack and stack[-1].live:
            return stack[-1].timed(phase, fn)
        return fn

    def _annotate(self, name: str) -> Any:
        """An entered ``jax.profiler.TraceAnnotation`` for a span that
        is being recorded (tens of ns while no profiler session is
        open), else None."""
        if not self._recording:
            return None
        cls = self._annotation_cls
        if cls is None:
            if "jax" not in sys.modules:
                return None
            with self._lock:
                self._hook_jax_locked()
                cls = self._annotation_cls
            if cls is None:  # configure("off") won the race
                return None
        annotation = cls(name)
        annotation.__enter__()
        return annotation

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """``gc.callbacks`` entry: one ``gc_pause`` span per collection
        of ``EXTERNAL_SPAN_MIN_S`` or longer, on the thread that ran it (the
        collector is not re-entrant, so one start time a thread is
        enough)."""
        if phase == "start":
            self._tls.gc_t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        t0 = getattr(self._tls, "gc_t0", None)
        if t0 is None or not self._recording:
            return
        self._record_interval(
            "gc_pause",
            t0,
            t1,
            {"generation": info["generation"], "collected": info["collected"]},
        )

    def _on_jax_duration(self, event: str, duration: float, **_: Any) -> None:
        """``jax.monitoring`` duration listener: jax reports a trace,
        a lowering or a backend compile when it ends, so the span is
        placed backwards from now. A jitted function traced inside
        another's trace nests inside it."""
        if not self._recording or not event.startswith(_COMPILE_EVENT_PREFIX):
            return
        t1 = time.perf_counter()
        self._record_interval("xla_compile", t1 - duration, t1, {"event": event})

    def _record_interval(
        self, name: str, t0: float, t1: float, args: Dict[str, Any]
    ) -> None:
        """A completed interval somebody else timed, recorded as a
        child of this thread's innermost open span."""
        if t1 - t0 < EXTERNAL_SPAN_MIN_S:
            return
        ev = {
            "name": name,
            "ph": "X",
            "pid": self._pid,
            "tid": threading.get_ident(),
            "ts": round((t0 - self._epoch) * 1e6, 3),
            "dur": round((t1 - t0) * 1e6, 3),
            "args": args,
        }
        stack = self._stack()
        if stack:
            top = stack[-1]
            args["parent"] = top.name
            if top.trace_id:
                ev["trace_id"] = top.trace_id
                ev["span_id"] = _new_span_id()
                ev["parent_span_id"] = top.span_id
        self._append(ev)

    def instant(self, name: str, **args: Any) -> None:
        """Zero-duration event (device health transitions etc.)."""
        flight = self._flight
        if flight is None and not self._recording:
            return
        now = time.perf_counter()
        if flight is not None:
            try:
                flight("instant", name, args, now, 0.0)
            except Exception:
                pass  # the post-mortem ring must not fail the op
        if not self._recording:
            return
        ev = {
            "name": name,
            "ph": "i",
            "s": "p",
            "pid": self._pid,
            "tid": threading.get_ident(),
            "ts": round((now - self._epoch) * 1e6, 3),
            "args": args,
        }
        stack = self._stack()
        if stack and stack[-1].trace_id:
            ev["trace_id"] = stack[-1].trace_id
            ev["parent_span_id"] = stack[-1].span_id
        self._append(ev)

    def _complete(self, span: _Span, t1: float) -> None:
        duration = t1 - span._t0
        observer = self._observer
        if observer is not None:
            try:
                observer(span.name, span.args, duration)
            except Exception:
                pass  # a broken metrics binding must not fail the traced op
        profile = self._profile
        if profile is not None:
            try:
                profile(span.name, span.args, duration)
            except Exception:
                pass  # a broken profiler must not fail the traced op
        flight = self._flight
        if flight is not None:
            try:
                flight("span", span.name, span.args, span._t0, duration)
            except Exception:
                pass  # the post-mortem ring must not fail the traced op
        if not self._recording:
            return
        args = span.args
        if span.parent:
            args.setdefault("parent", span.parent)
        ev = {
            "name": span.name,
            "ph": "X",
            "pid": self._pid,
            "tid": threading.get_ident(),
            "ts": round((span._t0 - self._epoch) * 1e6, 3),
            "dur": round(duration * 1e6, 3),
            "args": args,
        }
        if span.trace_id:
            ev["trace_id"] = span.trace_id
            ev["span_id"] = span.span_id
            if span.parent_span_id:
                ev["parent_span_id"] = span.parent_span_id
        self._append(ev)

    def _append(self, ev: Dict[str, Any]) -> None:
        tid = ev["tid"]
        name = threading.current_thread().name
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(ev)
            self.recorded += 1
            self._thread_names.setdefault(tid, name)

    # --- export --------------------------------------------------------------

    def _epoch_unix_us(self) -> float:
        """Wall-clock instant (unix microseconds) of the perf-counter
        epoch every event ``ts`` is relative to — the per-process anchor
        scripts/trace_merge.py uses for clock-skew correction."""
        return (time.time() - (time.perf_counter() - self._epoch)) * 1e6

    def _snapshot(
        self, limit: Optional[int], clear: bool
    ) -> "tuple[List[Dict[str, Any]], List[Dict[str, Any]], Dict[str, Any]]":
        """(meta_events, events, otherData) — the only part of an export
        that runs under the tracer lock is the ring copy."""
        with self._lock:
            events = list(self._ring)
            recorded, dropped = self.recorded, self.dropped
            names = dict(self._thread_names)
            if clear:
                self._ring.clear()
                self.dropped = 0
        if limit is not None and len(events) > limit:
            events = events[-limit:]
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": self._pid,
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in sorted(names.items())
        ]
        other = {
            "mode": self._mode,
            "recorded": recorded,
            "dropped": dropped,
            "pid": self._pid,
            "epoch_unix_us": round(self._epoch_unix_us(), 1),
            "epoch_perf_ns": self._epoch_ns,
        }
        return meta, events, other

    def export(
        self, limit: Optional[int] = None, clear: bool = False
    ) -> Dict[str, Any]:
        """Chrome ``trace_events`` JSON object; ``limit`` keeps the most
        recent N events (the response stays bounded)."""
        meta, events, other = self._snapshot(limit, clear)
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": other,
        }

    def export_chunks(
        self,
        limit: Optional[int] = None,
        clear: bool = False,
        fmt: str = "full",
    ) -> Iterator[bytes]:
        """Streamed export: the tracer lock is held only for the ring
        snapshot (O(events) pointer copies); all JSON serialization
        happens outside it, yielded in bounded chunks. ``fmt="chrome"``
        emits a pure Chrome/Perfetto document (no ``otherData``)."""
        meta, events, other = self._snapshot(limit, clear)
        yield b'{"traceEvents": ['
        first = True
        batch: List[str] = []
        for ev in meta + events:
            batch.append(("" if first else ",") + json.dumps(ev))
            first = False
            if len(batch) >= 256:
                yield "".join(batch).encode()
                batch = []
        if batch:
            yield "".join(batch).encode()
        tail = '], "displayTimeUnit": "ms"'
        if fmt != "chrome":
            tail += ', "otherData": %s' % json.dumps(other)
        yield (tail + "}").encode()

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-stage p50/p95/total over the ring's completed spans,
        grouped by the ``stage`` tag (falling back to the span name)."""
        with self._lock:
            events = [e for e in self._ring if e.get("ph") == "X"]
        groups: Dict[str, List[float]] = {}
        for ev in events:
            key = str(ev["args"].get("stage") or ev["name"])
            groups.setdefault(key, []).append(ev["dur"])
        out: Dict[str, Dict[str, float]] = {}
        for key in sorted(groups):
            durs = sorted(groups[key])
            n = len(durs)
            out[key] = {
                "count": n,
                "p50_ms": round(durs[n // 2] / 1e3, 4),
                "p95_ms": round(durs[min(n - 1, int(n * 0.95))] / 1e3, 4),
                "total_ms": round(sum(durs) / 1e3, 4),
            }
        return out

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome-trace JSON to ``path`` (default: the
        configured file mode's path). No-op without a destination."""
        path = path or self._path
        if not path:
            return None
        try:
            with open(path, "w") as f:
                json.dump(self.export(), f)
        except OSError:
            return None
        return path

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


def metrics_observer(ops=None, consensus=None):
    """Bridge span durations into the metric histograms: spans tagged
    ``stage`` + ``engine`` -> tendermint_ops_verify_stage_seconds, spans
    tagged ``step`` -> tendermint_consensus_step_duration_seconds. One
    timing source for both the trace and the histograms."""

    def observe(name: str, args: Dict[str, Any], seconds: float) -> None:
        stage = args.get("stage")
        engine = args.get("engine")
        if ops is not None and stage and engine:
            ops.verify_stage_seconds.labels(
                stage=str(stage), engine=str(engine)
            ).observe(seconds)
        step = args.get("step")
        if consensus is not None and step:
            consensus.step_duration_seconds.labels(step=str(step)).observe(
                seconds
            )

    return observe


# The process-wide instance every instrumentation site uses (the ops
# modules have no node handle — same pattern as device_policy.shared).
tracer = Tracer()
tracer.configure()


def configure(mode: Optional[str] = None) -> Tracer:
    return tracer.configure(mode)


def span(
    name: str, parent_ctx: Optional[TraceContext] = None, **args: Any
) -> Any:
    return tracer.span(name, parent_ctx=parent_ctx, **args)


def instant(name: str, **args: Any) -> None:
    tracer.instant(name, **args)


def tag(**tags: Any) -> None:
    tracer.tag(**tags)


def timed(phase: str, fn: Callable) -> Callable:
    return tracer.timed(phase, fn)


def attach(ctx: Optional[TraceContext]):
    """``with tracing.attach(ctx): ...`` — remote-parent splice."""
    return tracer.attach(ctx)


def current_context() -> Optional[TraceContext]:
    return tracer.current_context()
