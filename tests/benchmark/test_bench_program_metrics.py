"""The per-layer metrics read from the program's own flight records, on
the tiny cell: one traced run on the CPU, its result line, its
profiler trace and its flight records.

The program measures each block's state commit from inside: the
compile split into trace, lower and backend, the wait on the device,
and the keccak work it hands the kernels. The harness's own
instruments measure the same from outside, so they are the witness.
"""

import glob
import io
import os

import pytest
from jax.profiler import ProfileData

import benchtools
from benchmark import harness
from benchmark.trace_reduce import SPAN_WINDOW, reduce_dir

NEW = ("commit.trace_lower_ms", "commit.backend_compile_ms",
       "commit.device_wait_ms", "kernel.keccak_roofline")
HOST_EVENTS = ("resident/phase/compile_backend", "vm/buildBlock",
               "chain/execute")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny run: its result, the RunRecord the readers saw,
    and the directory of its profiler trace."""
    root = benchtools.tiny_root(tmp_path_factory.mktemp("cell"))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    seen = []
    window = harness.Session.window

    def keep(self, *a, **kw):
        rec = window(self, *a, **kw)
        seen.append(rec)
        return rec

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.Session, "window", keep)
        out, err = io.StringIO(), io.StringIO()
        res = harness.run_cell("tiny-transfers", 2 ** 31 + 11, 0.0, True,
                               root=root, require_tpu=False,
                               trace_dir=trace_dir, out=out, err=err)
    assert res["correct"], err.getvalue()
    return res, seen[0], trace_dir


def test_the_new_readers_report_on_a_traced_run(traced):
    res, rec, _ = traced
    m = res["metrics"]
    for name in NEW[:3]:
        assert m[name]["value"] > 0, name
    # the CPU has no device plane, so no device metric is written
    assert "kernel.keccak_roofline" not in m
    # each window block paid its one commit program's compile
    assert rec.counters["resident/plan_cache/misses"] == rec.n_blocks


def test_the_program_counts_the_keccak_work_the_harness_reads(traced):
    _, rec, _ = traced
    lanes = blocks = 0
    for r in rec.flight:
        for sec in (r, r["build"]):
            c = sec["counters"]
            lanes += c["resident/keccak/lanes"] + c["planned/keccak/lanes"]
            blocks += (c["resident/keccak/rate_blocks"]
                       + c["planned/keccak/rate_blocks"])
    assert rec.keccak["lanes"] > 0
    assert (lanes, blocks) == (rec.keccak["lanes"], rec.keccak["blocks"])


def test_every_window_block_has_its_build_in_its_flight_record(traced):
    _, rec, _ = traced
    assert len(rec.flight) == rec.n_blocks
    for r, b in zip(rec.flight, rec.blocks):
        build = r["build"]
        assert r["number"] == b["number"]
        covered = (build["phases"]["miner_execute"]
                   + build["phases"]["preverify"])
        assert covered <= b["build_s"]
        assert build["phases"]["preview_commit"] > 0
        assert build["resident"]["compile_backend"] > 0


def test_the_trace_holds_program_spans_inside_the_window(traced):
    _, rec, trace_dir = traced
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    window = None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                span = (e.start_ns, e.start_ns + e.duration_ns,
                        {k: v for k, v in e.stats})
                if e.name == SPAN_WINDOW:
                    window = span
                elif e.name in HOST_EVENTS:
                    events.setdefault(e.name, []).append(span)
    assert window is not None
    numbers = {b["number"] for b in rec.blocks}
    for name in HOST_EVENTS:
        inside = [s for s in events.get(name, [])
                  if window[0] <= s[0] and s[1] <= window[1]]
        assert inside, name
    assert {s[2]["number"] for s in events["vm/buildBlock"]
            if window[0] <= s[0]} == numbers
    # the reducer still reads the trace, the compile spans included
    assert reduce_dir(trace_dir).window_s > 0


class _Trace:
    devices = 1

    def __init__(self, secs):
        self.secs = secs

    def module_seconds(self, prefixes):
        return self.secs


def _record(flight, keccak, secs):
    rec = harness.RunRecord()
    rec.blocks = [{"gas_used": 1}] * max(1, len(flight))
    rec.window_s = 1.0
    rec.flight, rec.keccak = flight, keccak
    rec.trace = _Trace(secs)
    rec.device_kind = "TPU v5 lite"
    rec.peaks_table = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}
    return rec


def _counters(lanes, blocks, planned_lanes=0, planned_blocks=0):
    return {"resident/keccak/lanes": lanes,
            "resident/keccak/rate_blocks": blocks,
            "planned/keccak/lanes": planned_lanes,
            "planned/keccak/rate_blocks": planned_blocks}


def test_the_keccak_roofline_reads_what_the_harness_wraps_for():
    """With the program's counters equal to the executors' arguments,
    the two rooflines are the same number."""
    flight = [{"counters": _counters(0, 0),
               "build": {"counters": _counters(700, 900, 4, 8)}},
              {"counters": _counters(10, 20), "build": None}]
    rec = _record(flight, {"lanes": 714, "blocks": 928}, 0.003)
    cell = harness.Cell(benchtools.REPO, "transfers-sat")
    new = cell.reader("kernel.keccak_roofline")(rec)
    old = cell.reader("kernel.commit_program_roofline")(rec)
    assert new == pytest.approx(old) and new > 0


def test_timer_readers_sum_build_and_insert_per_block():
    flight = [{"resident": {"compile_trace": 1.0, "compile_lower": 0.5,
                            "compile_backend": 8.0, "wait": 0.002},
               "build": {"resident": {"compile_trace": 2.0,
                                      "compile_lower": 0.5,
                                      "compile_backend": 70.0,
                                      "wait": 0.004,
                                      "planned/compile_backend": 2.0}}},
              {"resident": {"compile_trace": 0.0, "compile_lower": 0.0,
                            "compile_backend": 0.0, "wait": 0.0},
               "build": None}]
    rec = _record(flight, {"lanes": 0, "blocks": 0}, 0.0)
    cell = harness.Cell(benchtools.REPO, "transfers-sat")
    assert cell.reader("commit.trace_lower_ms")(rec) \
        == pytest.approx(1000 * 4.0 / 2)
    assert cell.reader("commit.backend_compile_ms")(rec) \
        == pytest.approx(1000 * 80.0 / 2)
    assert cell.reader("commit.device_wait_ms")(rec) \
        == pytest.approx(1000 * 0.006 / 2)


def test_a_program_without_the_fields_reads_nothing():
    """The parent's records carry no build, compile split, wait or
    keccak counters: every new reader returns None and none raises."""
    flight = [{"phases": {"execute": 0.04}, "resident": {"plan": 0.001},
               "counters": {"resident/h2d_bytes": 5}}]
    rec = _record(flight, {"lanes": 714, "blocks": 928}, 0.003)
    cell = harness.Cell(benchtools.REPO, "transfers-sat")
    for name in NEW:
        assert cell.reader(name)(rec) is None, name
