"""Reduce a JAX profiler trace (`*.xplane.pb`) to the benchmark's numbers.

Read with `jax.profiler.ProfileData`. A device plane is one named
`/device:TPU:<n>`; on it, the `XLA Ops` line holds one event per
operation run, and the `XLA Modules` line one per program run. The
traced window is the host span `bench.window` that the harness writes.

  busy_s      union of the device's operation intervals inside the
              window, averaged over the device planes
  window_s    length of the window
  top_ops     device seconds by operation name, largest first
  modules     device seconds by program name
  idle_gaps   seconds in which no operation ran, by the innermost host
              span that covered each moment of the gap: the harness's
              own spans, and JAX's compile events as "compile" ("other"
              where none did), largest first
"""

from __future__ import annotations

import glob
import os

# the harness's own spans: it writes them into the trace, and they label
# the device's idle gaps
SPAN_WINDOW = "bench.window"
SPAN_TOP_UP = "bench.pool_top_up"
SPAN_BUILD = "vm.build_block"
SPAN_ACCEPT = "vm.verify_accept"
HOST_SPANS = (SPAN_TOP_UP, SPAN_BUILD, SPAN_ACCEPT)
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Summary:
    def __init__(self, busy_s, window_s, top_ops, modules, idle_gaps,
                 devices):
        self.busy_s = busy_s
        self.window_s = window_s
        self.top_ops = top_ops
        self.modules = modules
        self.idle_gaps = idle_gaps
        self.devices = devices

    def module_seconds(self, prefixes) -> float:
        """Device seconds of the programs whose names start with one of
        the prefixes, averaged over the devices."""
        return sum(s for name, s in self.modules.items()
                   if name.startswith(tuple(prefixes)))


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint [start, end] pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label_gaps(gaps, spans) -> dict:
    """Seconds of gap time under each host span: where spans nest, the
    one that started last (the innermost) takes the time."""
    bounds = sorted({t for g in gaps for t in g}
                    | {t for s, e, _ in spans for t in (s, e)})
    spans = sorted(spans)
    out: dict = {}
    gi = 0
    for a, b in zip(bounds, bounds[1:]):
        while gi < len(gaps) and gaps[gi][1] <= a:
            gi += 1
        if gi == len(gaps) or gaps[gi][0] >= b:
            continue
        mid = (a + b) / 2
        label = "other"
        for s, e, name in spans:
            if s > mid:
                break
            if e > mid:
                label = name
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    return out


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in ln.events]
                     for ln in plane.lines}
            if OPS_LINE in lines or MODULES_LINE in lines:
                devices.append(lines)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SPAN_WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in HOST_SPANS:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
                elif "compile" in e.name.lower():
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  "compile"))
    if window is None:
        raise ValueError(f"{path}: no {SPAN_WINDOW} span in the trace")
    lo, hi = window
    busy_total, ops, modules, gaps_all = 0.0, {}, {}, {}
    for lines in devices:
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        busy = union(_clip([(s, e) for s, e, _ in evs], lo, hi))
        busy_total += sum(e - s for s, e in busy) / 1e9
        for s, e, name in evs:
            if e > lo and s < hi:
                ops[name] = ops.get(name, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        for s, e, name in lines.get(MODULES_LINE, []):
            if e > lo and s < hi:
                modules[name] = modules.get(name, 0.0) \
                    + (min(e, hi) - max(s, lo)) / 1e9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for label, secs in _label_gaps(gaps, spans).items():
            gaps_all[label] = gaps_all.get(label, 0.0) + secs
    n = max(len(devices), 1)
    return Summary(
        busy_s=busy_total / n,
        window_s=(hi - lo) / 1e9,
        top_ops=sorted(([k, v / n] for k, v in ops.items()),
                       key=lambda kv: -kv[1]),
        modules={k: v / n for k, v in modules.items()},
        idle_gaps=sorted(([k, v / n] for k, v in gaps_all.items()),
                         key=lambda kv: -kv[1]),
        devices=len(devices))


def reduce_dir(trace_dir: str) -> Summary:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(paths[-1])
