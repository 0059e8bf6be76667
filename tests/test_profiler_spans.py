"""Program spans in the JAX profiler's trace.

While a `jax.profiler` session records, every `span(...)` (and every
`phase_timer`, which opens one) writes a TraceMe of the same name, its
attributes as metadata, into the session's host plane, whether or not
the span ring is on. With no session and the ring off, `span()` is the
shared null span: no allocation, no clock read, nothing written.
"""

import glob
import itertools
import os
import tracemalloc

import jax
import pytest
from jax.profiler import ProfileData

from coreth_tpu.metrics import default_registry, phase_timer
from coreth_tpu.metrics import spans as spans_mod
from coreth_tpu.metrics.spans import _NULL_SPAN, span

WINDOW = "bench.window"


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every event off the device
    planes of the one xplane under trace_dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            {k: v for k, v in e.stats}))
    return out


def _record(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


@pytest.mark.parametrize("ring", [False, True])
def test_spans_land_in_the_session_inside_the_window(tmp_path, ring):
    def body():
        with span("vm/buildBlock", number=7, host_mode=False):
            with phase_timer("resident/phase/compile_backend"):
                jax.numpy.ones(8).block_until_ready()

    before = default_registry.timer("resident/phase/compile_backend").count()
    spans_mod.set_enabled(ring)
    try:
        spans_mod.tracer.clear()
        events = _record(str(tmp_path), body)
        ringed = [s.name for s in spans_mod.tracer.snapshot(clear=True)]
    finally:
        spans_mod.set_enabled(False)
    window = [e for e in events if e[0] == WINDOW]
    assert len(window) == 1
    lo, hi = window[0][1], window[0][2]
    build = [e for e in events if e[0] == "vm/buildBlock"]
    assert len(build) == 1
    assert build[0][3]["number"] == 7
    assert lo <= build[0][1] and build[0][2] <= hi
    phase = [e for e in events if e[0] == "resident/phase/compile_backend"]
    assert len(phase) == 1
    assert build[0][1] <= phase[0][1] and phase[0][2] <= build[0][2]
    # the timer still counts, and the ring holds the spans only when on
    assert default_registry.timer(
        "resident/phase/compile_backend").count() == before + 1
    if ring:
        assert ringed == ["resident/phase/compile_backend", "vm/buildBlock"]
    else:
        assert ringed == []


def test_set_attr_reaches_the_session(tmp_path):
    def body():
        with span("exec/parallel/worker", worker=2) as sp:
            sp.set_attr("txs", 5)

    events = _record(str(tmp_path), body)
    (worker,) = [e for e in events if e[0] == "exec/parallel/worker"]
    assert worker[3]["worker"] == 2 and worker[3]["txs"] == 5


def test_off_without_a_session_writes_nothing(tmp_path):
    assert not spans_mod.enabled
    # a span opened with no session is not in a later session's trace
    with span("obs/before_session", number=1):
        pass
    events = _record(str(tmp_path), lambda: None)
    assert not any(e[0] == "obs/before_session" for e in events)
    assert span("obs/after_session") is _NULL_SPAN
    assert spans_mod.tracer.snapshot() == []


def _spans(calls):
    sp = None
    for _ in calls:
        sp = span("obs/off")
    return sp


def test_off_without_a_session_reads_no_clock_and_allocates_nothing(
        monkeypatch):
    # a first pass binds the profiler check and warms the loop's code
    assert _spans(itertools.repeat(None, 100)) is _NULL_SPAN

    class NoClock:
        def monotonic(self):
            raise AssertionError("span() read the clock")

    monkeypatch.setattr(spans_mod, "time", NoClock())
    peaks = []
    tracemalloc.start()
    try:
        for n in (100, 20000):  # the first pass under tracing warms it
            calls = itertools.repeat(None, n)
            tracemalloc.reset_peak()
            base, _ = tracemalloc.get_traced_memory()
            sp = _spans(calls)
            _, peak = tracemalloc.get_traced_memory()
            peaks.append(peak - base)
    finally:
        tracemalloc.stop()
    assert sp is _NULL_SPAN
    assert peaks[-1] == 0
