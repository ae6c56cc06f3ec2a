"""Phase spans: ONE bracket, on the profiler's clock.

`Span` (handed out by `Server._span(name, hist=None, wait=False,
work=None)`) is the only way the program times a phase. Entering it

  1. enters `jax.profiler.TraceAnnotation("adapm." + name)`: with no
     profiler session that is a level check; inside one (any
     `jax.profiler.start_trace`, or `benchmarks/run.py --trace 1`) it
     is an event on the host plane of the same `.xplane.pb` as the
     device's `XLA Ops` — host phases and device operations on one
     clock. The profiler session is the switch: there is no flag;
  2. if `hist` is given, observes the elapsed seconds into that
     registry histogram on exit (the null metric under
     `--sys.metrics 0`);
  3. if `--sys.trace.spans` is on, records into the `SpanTracer` below
     (breadcrumb + Chrome JSON for operators, docs/OBSERVABILITY.md).

Waits and self time (ISSUE 35). A span's length is not host work while
the device sets the pace: a dispatch waits for a free slot, an upload
for the transfer, a read-back for the step before it. So

  - a span marked `wait=True` brackets ONE call through which the host
    can block on the device or on a transfer, and no work of the
    program's own. On exit it adds its elapsed seconds to a per-thread
    tally (`_WAITED.s`, monotone);
  - a span given a `work` histogram reads that tally at entry and at
    exit and observes `elapsed - growth` into `work`: its time outside
    every wait beneath it, at any depth, on its own thread. `hist`
    keeps the whole elapsed time, from the same two stamps, so
    `hist.sum - work.sum` is exactly the waits beneath it;
  - a span with neither touches no tally (the serve path's brackets);
    under `--sys.metrics 0` the server hands out neither.

A bracketed call also does host work that Python cannot split off (a
`device_put` copies into a staging buffer before it returns, a jit call
flattens its operands), so a wait span is an UPPER bound of waiting and
`*_work_s` a LOWER bound of the host's own (PERF.md section 3 has the
floor, measured with nothing in flight).

Rule for names and nesting: no span may enclose a whole pass, epoch or
step loop — the outermost span on a thread is ONE phase of a step or of
a micro-batch (an idle gap is attributed to the host event that
overlaps it most, and an enclosing span would swallow every gap under
it).

`SpanTracer` stores completed spans as (thread, name, start_us, dur_us)
tuples and exports them as Chrome trace-event JSON (`ph: "X"` complete
events + thread-name metadata), which chrome://tracing and
https://ui.perfetto.dev load directly. Off by default
(`--sys.trace.spans`); when off the Server holds no tracer.

Crash breadcrumb (ISSUE 2 satellite): when given a breadcrumb path, the
tracer overwrites a small fixed-size file with the span name + wall time
at every span BEGIN (one `pwrite`, no seek state). After a hard abort —
this image's XLA CPU segfaults intermittently on pre-existing
checkpoint-restore paths (CHANGES.md r6) — the file names the phase the
process died inside, complementing the faulthandler stack
(obs/crash.py).

Memory is bounded: beyond `max_events` spans
(`--sys.trace.spans.max_events`, validated >= 1000 in config.py), new
ones are counted as dropped instead of stored — loudly: one warning
log on the first drop plus the `spans.dropped` registry counter
(ISSUE 17 satellite; the old behavior capped silently at a hardcoded
1M), and the exported trace states the truncation.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

_BREADCRUMB_WIDTH = 256


class _Waited(threading.local):
    """Seconds this thread has spent inside wait spans (monotone)."""
    s = 0.0


_WAITED = _Waited()


class Span(TraceAnnotation):
    """The phase bracket (module docstring): profiler annotation always,
    histogram observation when `hist` is given, `SpanTracer` record
    when the server holds a tracer; `wait` adds the elapsed seconds to
    the thread's wait tally, `work` observes the elapsed seconds less
    the tally's growth."""

    __slots__ = ("_name", "_hist", "_tracer", "_t0", "_wait", "_work",
                 "_w0", "_timed")

    def __init__(self, name: str, hist=None,
                 tracer: Optional["SpanTracer"] = None,
                 wait: bool = False, work=None):
        super().__init__("adapm." + name)
        self._name = name
        self._hist = hist
        self._tracer = tracer
        self._wait = wait
        self._work = work
        self._timed = hist is not None or wait or work is not None
        self._t0 = self._w0 = 0.0

    def __enter__(self):
        super().__enter__()
        if self._tracer is not None:
            self._t0 = self._tracer.begin(self._name)
        elif self._timed:
            self._t0 = time.perf_counter()
        if self._work is not None:
            self._w0 = _WAITED.s
        return self

    def __exit__(self, *exc):
        if self._timed:
            dt = time.perf_counter() - self._t0
            if self._hist is not None:
                self._hist.observe(dt)
            if self._wait:
                _WAITED.s += dt
            if self._work is not None:
                self._work.observe(dt - (_WAITED.s - self._w0))
        if self._tracer is not None:
            self._tracer.end(self._name, self._t0)
        super().__exit__(*exc)
        return False


class SpanTracer:
    def __init__(self, rank: int = 0, max_events: int = 1_000_000,
                 breadcrumb_path: Optional[str] = None, registry=None):
        self.rank = rank
        self.max_events = max_events
        self.dropped = 0
        # overflow drops are loud (ISSUE 17 satellite): a registry
        # counter when the server's registry is live, else the plain
        # `dropped` tally alone (spans.* names exist only while a
        # tracer does — the skip-wrapper naming discipline)
        self._c_dropped = None
        if registry is not None and registry.enabled:
            self._c_dropped = registry.counter("spans.dropped")
        self._warned_drop = False
        # (tid, name, t0_us, dur_us); list.append is atomic under the GIL
        self._events: List[Tuple[int, str, float, float]] = []
        self._t0 = time.perf_counter()
        self._bc_fd = None
        self._bc_path = breadcrumb_path
        if breadcrumb_path:
            self._bc_fd = os.open(breadcrumb_path,
                                  os.O_CREAT | os.O_WRONLY, 0o644)

    # -- recording -----------------------------------------------------------

    def begin(self, name: str) -> float:
        if self._bc_fd is not None:
            line = (f"{name} thread={threading.current_thread().name} "
                    f"wall={time.time():.3f}\n").encode()
            os.pwrite(self._bc_fd, line.ljust(_BREADCRUMB_WIDTH), 0)
        return time.perf_counter()

    def end(self, name: str, t0: float) -> None:
        t1 = time.perf_counter()
        if len(self._events) >= self.max_events:
            self.dropped += 1
            if self._c_dropped is not None:
                self._c_dropped.inc()
            if not self._warned_drop:
                self._warned_drop = True
                from ..utils import alog
                alog(f"[spans] event buffer full ({self.max_events} "
                     f"spans; --sys.trace.spans.max_events); further "
                     f"spans are DROPPED (counted in spans.dropped) — "
                     f"the exported trace is a loud prefix, not a "
                     f"silent lie")
            return
        self._events.append((threading.get_ident(), name,
                             (t0 - self._t0) * 1e6, (t1 - t0) * 1e6))

    # -- export --------------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        return {"events": len(self._events), "dropped": self.dropped}

    def export(self, path: str) -> str:
        """Write Chrome trace-event JSON; returns the path."""
        events = list(self._events)
        tids: Dict[int, int] = {}
        names: Dict[int, str] = {t.ident: t.name
                                 for t in threading.enumerate()
                                 if t.ident is not None}
        out = []
        for ident, name, ts, dur in events:
            tid = tids.setdefault(ident, len(tids))
            out.append({"name": name, "cat": "adapm", "ph": "X",
                        "ts": round(ts, 3), "dur": round(dur, 3),
                        "pid": self.rank, "tid": tid})
        meta = [{"name": "thread_name", "ph": "M", "pid": self.rank,
                 "tid": tid,
                 "args": {"name": names.get(ident, f"thread-{ident}")}}
                for ident, tid in tids.items()]
        meta.append({"name": "process_name", "ph": "M", "pid": self.rank,
                     "args": {"name": f"adapm rank {self.rank}"}})
        doc = {"traceEvents": meta + out, "displayTimeUnit": "ms"}
        if self.dropped:
            doc["adapm_dropped_events"] = self.dropped
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def close(self) -> None:
        if self._bc_fd is not None:
            os.close(self._bc_fd)
            self._bc_fd = None
