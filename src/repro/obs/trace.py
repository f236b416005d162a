"""Span-based tracing of the round pipeline, exported as Chrome-trace JSON
(loadable in ``chrome://tracing`` and Perfetto).

The tracer is deliberately tiny: a span is one appended tuple on exit, and
call sites hold a tracer reference that defaults to ``NULL_TRACER`` — whose
``span()`` returns a shared no-op context manager, so the disabled fast
path costs a single attribute lookup + two empty calls per span.

**Fencing.**  jax dispatch is asynchronous: a span closing right after a
jitted call measures *submission*, not execution.  ``Tracer(fence=True)``
makes ``tracer.fence(x)`` call ``jax.block_until_ready`` on ``x`` so span
timings are honest on device, at the cost of serializing the pipeline —
opt-in, off by default, and a no-op identity on the null tracer.

**One clock with the device.**  While the JAX profiler is recording, a
``Tracer`` span also enters a ``jax.profiler.TraceAnnotation`` of the same
name, so every span lands on the profile's host ``python`` line, on the
clock of the device planes.  Retroactive ``complete()`` events (compiles)
and instants stay in this tracer only.  JAX is imported on the first span,
so importing this module needs no backend.
"""
from __future__ import annotations

import json
import time


_annotation = None       # jax.profiler.TraceAnnotation, bound on first use


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _Span:
    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer, name, cat, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        ann = _annotation or _trace_annotation()
        # the annotation is made only while the profiler records: it opens
        # before this span's clock reading and closes after its last one
        self._ann = ann(self.name) if ann.is_enabled() else None
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._tracer._events.append(
            (self.name, self.cat, self._t0, t1 - self._t0, self.args))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class _NullSpan:
    """Shared no-op span: the single-branch disabled fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every op is a no-op, ``fence`` is identity."""
    __slots__ = ()
    enabled = False
    fencing = False

    def span(self, name, cat="sim", **args):
        return _NULL_SPAN

    def instant(self, name, cat="sim", **args):
        pass

    def complete(self, name, t0_ns, dur_ns, cat="sim", **args):
        pass

    def fence(self, value):
        return value

    def events(self):
        return []


NULL_TRACER = NullTracer()


class Tracer:
    """Recording tracer.  ``span(name)`` is a context manager; nesting is
    implied by interval containment (all spans are synchronous on one
    host thread, so Chrome/Perfetto reconstruct the stack from overlap)."""
    __slots__ = ("_events", "_origin_ns", "fencing")
    enabled = True

    def __init__(self, fence: bool = False):
        self._events = []          # (name, cat, t0_ns, dur_ns, args|None)
        self._origin_ns = time.perf_counter_ns()
        self.fencing = fence

    def span(self, name, cat="sim", **args):
        return _Span(self, name, cat, args or None)

    def instant(self, name, cat="sim", **args):
        self._events.append(
            (name, cat, time.perf_counter_ns(), 0, args or None))

    def complete(self, name, t0_ns, dur_ns, cat="sim", **args):
        """Record a span retroactively from caller-measured timestamps
        (``time.perf_counter_ns()``) — used where a context manager can't
        wrap the timed region, e.g. lazily-detected XLA compiles."""
        self._events.append((name, cat, t0_ns, dur_ns, args or None))

    def fence(self, value):
        """Block until ``value`` (any jax pytree) is computed when fencing
        is enabled — call inside a span to make its duration cover device
        execution, not just dispatch."""
        if self.fencing and value is not None:
            import jax
            jax.block_until_ready(value)
        return value

    # ------------------------------------------------------------ export
    def events(self) -> list[dict]:
        """Chrome-trace event dicts (ts/dur in µs from the tracer origin)."""
        o = self._origin_ns
        out = []
        for name, cat, t0, dur, args in sorted(self._events,
                                               key=lambda e: e[2]):
            ev = {"name": name, "cat": cat, "ph": "X",
                  "ts": (t0 - o) / 1e3, "dur": dur / 1e3,
                  "pid": 0, "tid": 0}
            if args:
                ev["args"] = {k: _jsonable(v) for k, v in args.items()}
            out.append(ev)
        return out

    def to_chrome(self) -> dict:
        meta = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                 "args": {"name": "fedrac"}}]
        return {"traceEvents": meta + self.events(),
                "displayTimeUnit": "ms"}

    def write(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


def _jsonable(v):
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    return str(v)


def span_coverage(events: list[dict], root: str) -> float:
    """Fraction of the ``root`` span's duration covered by the union of the
    other spans nested inside it (nesting = interval containment, so doubly
    counted children collapse in the union).  Used by the validator to
    assert the trace accounts for ≥95% of measured wall-clock."""
    roots = [e for e in events
             if e.get("ph") == "X" and e["name"] == root]
    if not roots:
        raise ValueError(f"no {root!r} span in trace")
    r = roots[0]
    lo, hi = r["ts"], r["ts"] + r["dur"]
    if r["dur"] <= 0:
        return 1.0
    ivals = sorted((max(e["ts"], lo), min(e["ts"] + e["dur"], hi))
                   for e in events
                   if e.get("ph") == "X" and e is not r
                   and e["ts"] >= lo and e["ts"] + e["dur"] <= hi)
    covered, cur_lo, cur_hi = 0.0, None, None
    for a, b in ivals:
        if cur_lo is None:
            cur_lo, cur_hi = a, b
        elif a <= cur_hi:
            cur_hi = max(cur_hi, b)
        else:
            covered += cur_hi - cur_lo
            cur_lo, cur_hi = a, b
    if cur_lo is not None:
        covered += cur_hi - cur_lo
    return covered / r["dur"]
