"""Headline benchmark: trie-root commitment nodes/sec, TPU vs CPU.

The workload is BASELINE.json config #2 scaled by CORETH_TPU_BENCH_LEAVES:
an N-account state trie's full dirty-set commit. Both pipelines share the
native planner (native/mpt.cpp — trie shape + node RLP + segment layout,
the host work the reference does inside its hash walk,
trie/trie.go:573-626 + trie/hasher.go:195-201) and are timed END TO END
from the sorted leaf arrays to the 32-byte root:

  cpu: plan + threaded-C++ keccak over every level (the reference's
       16-goroutine fan-out collapsed onto this host's cores)
  tpu: plan + ONE bulk u32 transfer + per-segment device dispatches with
       on-device digest patching (ops/keccak_planned.py) — the SAME
       executor the production chain runs under device_hasher="planned"
       (trie/planned.py, state/statedb.py _planned_intermediate_root)

Run it as one process on a TPU host: `python bench.py` (`--early` for the
small leg only). It refuses to run when JAX's default device is not a
TPU. Host-side results (CPU rate, plan/export timings) are measured first;
every device phase then runs under a watchdog whose expiry emits the
partial report and exits 3. The Pallas segment kernel carries the device
legs (CORETH_TPU_BENCH_KERNEL=xla selects the XLA kernel instead); a
kernel or leg that fails ends the run with exit 1.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...diag};
vs_baseline = tpu_rate / cpu_rate on the same workload (>1 is a win).
Roots are asserted bit-identical before any number is reported.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

REPORT = {
    "metric": "trie_commit_nodes_per_sec",
    "value": 0.0,
    "unit": "nodes/s",
    "vs_baseline": 0.0,
}

_EMIT_LOCK = threading.Lock()
_EMITTED = False
_ACTIVE_WATCHDOG: "PhaseWatchdog | None" = None


def emit(error: str | None = None, code: int | None = None):
    """Print the single report line exactly once (watchdog thread and main
    thread can race here; first caller wins, the other is a no-op)."""
    global _EMITTED
    if _ACTIVE_WATCHDOG is not None:
        _ACTIVE_WATCHDOG.cancel()
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        if error:
            REPORT["error"] = error
        print(json.dumps(dict(REPORT)), flush=True)
    if code is not None:
        os._exit(code)


class PhaseWatchdog:
    """One phase at a time; firing emits the partial report and exits."""

    def __init__(self, deadline: float):
        self._timer = None
        self._deadline = deadline  # absolute wall-clock budget for the run

    def arm(self, phase: str, seconds: float):
        self.cancel()
        remaining = self._deadline - time.monotonic()
        budget = max(5.0, min(seconds, remaining))
        self._timer = threading.Timer(
            budget,
            lambda: emit(
                f"device hung during phase {phase!r} "
                f"(no progress within {budget:.0f}s; partial results above "
                "are real)",
                code=3,
            ),
        )
        self._timer.daemon = True
        self._timer.start()

    def cancel(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None


def build_workload(n_leaves: int, seed: int = 1):
    """Sorted (keys, vals, offsets) numpy arrays — the shape StateDB hands
    the committer (account hashes are already keccak outputs, so random
    bytes model them exactly)."""
    import random

    from coreth_tpu.native.mpt import items_to_arrays

    rng = random.Random(seed)
    items = [
        (rng.randbytes(32), rng.randbytes(rng.randint(40, 90)))
        for _ in range(n_leaves)
    ]
    return items_to_arrays(items)


def best_of(fn, repeats: int):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        r = fn()
        dt = time.perf_counter() - t0
        if dt < best:
            best = dt
        assert out is None or r == out, "nondeterministic result"
        out = r
    return best, out


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        sys.exit(2)
    REPORT["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    t_start = time.monotonic()
    # --early: a device number + Pallas parity in minutes (small leg
    # only, no big/incremental)
    early = "--early" in sys.argv
    default_deadline = "600" if early else "1500"
    deadline = t_start + float(
        os.environ.get("CORETH_TPU_BENCH_DEADLINE", default_deadline))
    n_big = int(os.environ.get("CORETH_TPU_BENCH_LEAVES", "200000"))
    n_small = int(os.environ.get("CORETH_TPU_BENCH_SMALL_LEAVES", "20000"))
    if early:
        n_big = n_small
        REPORT["mode"] = "early"
    repeats = int(os.environ.get("CORETH_TPU_BENCH_REPEATS", "3"))
    from coreth_tpu.native import default_cpu_threads

    cpu_threads = int(
        os.environ.get("CORETH_TPU_BENCH_CPU_THREADS", "0")
    ) or default_cpu_threads()
    kernel_env = os.environ.get("CORETH_TPU_BENCH_KERNEL", "")  # "", xla, pallas

    # ------------------------------------------------ host-only phase first
    import numpy as np

    from coreth_tpu.native.mpt import load, plan_commit

    workloads = {}
    for name, n in (("small", n_small), ("big", n_big)):
        keys, vals, off = build_workload(n)
        t0 = time.perf_counter()
        plan = plan_commit(keys, vals, off)
        plan_s = time.perf_counter() - t0
        phases = np.zeros(3)
        load().mpt_plan_last_timings(phases)
        REPORT[f"{name}_plan_phases_ms"] = [round(x * 1e3, 1) for x in phases]
        cpu_s, cpu_root = best_of(
            lambda k=keys, v=vals, o=off: plan_commit(k, v, o).execute_cpu(
                threads=cpu_threads
            ),
            repeats,
        )
        workloads[name] = {
            "arrays": (keys, vals, off),
            "nodes": plan.num_nodes,
            "cpu_s": cpu_s,
            "cpu_root": cpu_root,
        }
        REPORT[f"{name}_leaves"] = n
        REPORT[f"{name}_nodes"] = plan.num_nodes
        REPORT[f"{name}_plan_ms"] = round(plan_s * 1e3, 1)
        REPORT[f"{name}_cpu_nodes_per_sec"] = round(plan.num_nodes / cpu_s, 1)
        del plan

    big = workloads["big"]
    REPORT["cpu_nodes_per_sec"] = REPORT["big_cpu_nodes_per_sec"]
    REPORT["cpu_threads"] = cpu_threads
    if cpu_threads > 1:
        # single-thread oracle leg: the threaded/1T ratio is the native
        # worker-pool win, with the root re-asserted against the same plan
        k, v, o = big["arrays"]
        cpu1_s, cpu1_root = best_of(
            lambda: plan_commit(k, v, o).execute_cpu(threads=1), repeats)
        assert cpu1_root == big["cpu_root"], "threaded root mismatch vs 1T"
        REPORT["cpu_1t_nodes_per_sec"] = round(big["nodes"] / cpu1_s, 1)
        REPORT["cpu_mt_speedup"] = round(cpu1_s / big["cpu_s"], 3)

    # ------------------------------------------------- device legs
    global _ACTIVE_WATCHDOG
    wd = PhaseWatchdog(deadline)
    _ACTIVE_WATCHDOG = wd
    from coreth_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    from coreth_tpu.ops.keccak_planned import PlannedCommit

    kernel = "xla" if kernel_env == "xla" else "pallas"
    REPORT["kernel"] = kernel
    if kernel == "pallas":
        # parity of the kernel against the XLA scan before any leg
        import numpy as np

        from coreth_tpu.ops.keccak_pallas import staged_seg_impl
        from coreth_tpu.ops.keccak_staged import _segment_keccak

        wd.arm("pallas-parity", 600)
        words = np.random.default_rng(0).integers(
            0, 2**32, size=(1024, 2, 34), dtype=np.uint32)
        if not (np.asarray(staged_seg_impl()(words))
                == np.asarray(_segment_keccak(words))).all():
            raise AssertionError("pallas/XLA segment digest mismatch")
        planned = PlannedCommit(seg_impl=staged_seg_impl())
    else:
        planned = PlannedCommit()

    # micro decomposition FIRST: link bandwidth, dispatch round-trip, and
    # kernel-only throughput land before any leg
    measure_micro(wd, kernel)

    def run_device(name):
        keys, vals, off = workloads[name]["arrays"]
        p = plan_commit(keys, vals, off)
        root = p.execute_planned(planned)
        workloads[name]["h2d_bytes"] = planned.last_h2d_bytes
        workloads[name]["dispatches"] = planned.last_dispatches
        workloads[name]["transfers"] = planned.last_transfers
        workloads[name]["segments"] = len(p.export_words()[0])
        return root

    # small leg: compile + land a device number before the big attempt
    wd.arm("small-warmup", 480)
    root = run_device("small")
    assert root == workloads["small"]["cpu_root"], "small root mismatch"
    wd.arm("small-measure", 300)
    small_s, root = best_of(lambda: run_device("small"), repeats)
    assert root == workloads["small"]["cpu_root"]
    small = workloads["small"]
    REPORT["small_tpu_nodes_per_sec"] = round(small["nodes"] / small_s, 1)
    REPORT["small_dispatches"] = small["dispatches"]
    REPORT["small_transfers"] = small["transfers"]
    REPORT["small_segments"] = small["segments"]
    REPORT["small_h2d_mb"] = round(small["h2d_bytes"] / 1e6, 2)
    if REPORT.get("h2d_mb_per_sec"):
        # how much of the measured wall is pure link time at measured BW
        REPORT["small_link_s_at_measured_bw"] = round(
            small["h2d_bytes"] / 1e6 / REPORT["h2d_mb_per_sec"], 3)
    REPORT["value"] = REPORT["small_tpu_nodes_per_sec"]
    REPORT["vs_baseline"] = round(small["cpu_s"] / small_s, 3)
    REPORT["scope"] = "small"

    if early:
        wd.cancel()
        REPORT["total_s"] = round(time.monotonic() - t_start, 1)
        emit()
        return

    # big leg
    wd.arm("big-warmup", 600)
    root = run_device("big")
    assert root == big["cpu_root"], "big root mismatch"
    wd.arm("big-measure", 480)
    big_s, root = best_of(lambda: run_device("big"), repeats)
    assert root == big["cpu_root"]
    REPORT["big_tpu_nodes_per_sec"] = round(big["nodes"] / big_s, 1)
    REPORT["big_dispatches"] = big["dispatches"]
    REPORT["big_transfers"] = big["transfers"]
    REPORT["big_segments"] = big["segments"]
    REPORT["big_h2d_mb"] = round(big["h2d_bytes"] / 1e6, 2)
    if REPORT.get("h2d_mb_per_sec"):
        REPORT["big_link_s_at_measured_bw"] = round(
            big["h2d_bytes"] / 1e6 / REPORT["h2d_mb_per_sec"], 3)
    REPORT["value"] = REPORT["big_tpu_nodes_per_sec"]
    REPORT["vs_baseline"] = round(big["cpu_s"] / big_s, 3)
    REPORT["scope"] = "big"

    # ------------------------------------------- resident-commit leg
    # The deferred-absorb + template-residency design (VERDICT r4 items
    # 1+2): device-persistent digest store + row arenas, delta patches,
    # pipelined dispatch (roots checked with one commit of lag). This is
    # the leg that must win at 90 MB/s-class bandwidth.
    res_result = run_resident(wd, planned_kernel=kernel)
    REPORT.update(res_result)
    if res_result["res_vs_cpu"] > REPORT["vs_baseline"]:
        REPORT["value"] = res_result["res_tpu_nodes_per_sec"]
        REPORT["vs_baseline"] = res_result["res_vs_cpu"]
        REPORT["scope"] = f"resident-{res_result['res_leaves']}"

    # ------------------------------------------- incremental-commit leg
    # BASELINE's north-star workload shape: a 1M-account trie committed
    # repeatedly with K-account churn. Both sides keep the trie warm and
    # re-hash ONLY the dirty subtree (the reference's trie/trie.go:573-626
    # semantics); the device side ships the dirty mini-plan through the
    # same planned executor the chain runs.
    inc_result = run_incremental(wd, planned)
    REPORT.update(inc_result)
    # headline = the better honest leg; both stay in the report
    if inc_result["inc_vs_cpu"] > REPORT["vs_baseline"]:
        REPORT["value"] = inc_result["inc_tpu_nodes_per_sec"]
        REPORT["vs_baseline"] = inc_result["inc_vs_cpu"]
        REPORT["scope"] = f"incremental-{inc_result['inc_leaves']}"

    wd.cancel()
    REPORT["total_s"] = round(time.monotonic() - t_start, 1)
    emit()


def measure_micro(wd, kernel):
    """Link/dispatch/kernel decomposition. Each number is independent of
    the commit legs:

      device_roundtrip_ms    dispatch+sync floor (tiny jitted op, d2h)
      h2d_mb_per_sec         achieved host->device bandwidth (32 MiB put)
      d2h_mb_per_sec         achieved device->host bandwidth
      kernel_hashes_per_sec  keccak-f[1600] permutations/s with transfers
                             excluded (device-resident input, 16 queued
                             dispatches, one sync)
      kernel_mb_per_sec      same, as absorbed padded-message bytes
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    # dispatch round-trip floor
    wd.arm("micro-roundtrip", 120)
    tiny = jax.device_put(np.zeros(8, np.uint32))
    bump = jax.jit(lambda x: x + 1)
    np.asarray(bump(tiny))  # compile
    rt, _ = best_of(lambda: (np.asarray(bump(tiny)), 0)[1], 5)
    REPORT["device_roundtrip_ms"] = round(rt * 1e3, 2)

    # link bandwidth, both directions (32 MiB payload)
    wd.arm("micro-link", 180)
    buf = np.random.default_rng(0).integers(
        0, 2**32, size=(8 << 20,), dtype=np.uint32)  # 32 MiB
    jax.device_put(buf).block_until_ready()  # first put may init pools
    t, _ = best_of(
        lambda: (jax.device_put(buf).block_until_ready(), 0)[1], 3)
    REPORT["h2d_mb_per_sec"] = round(buf.nbytes / 1e6 / t, 1)
    # fresh device array per repeat: jax.Array caches its host copy
    # after the first np.asarray, which would turn repeats 2..n into
    # memcpy-speed cache hits and corrupt the link attribution
    best = float("inf")
    for _ in range(3):
        dev = jax.device_put(buf)
        dev.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(dev)
        best = min(best, time.perf_counter() - t0)
        del dev
    REPORT["d2h_mb_per_sec"] = round(buf.nbytes / 1e6 / best, 1)

    # kernel-only keccak throughput: device-resident input, transfers
    # excluded; 16 dispatches queued, one synchronization
    wd.arm("micro-kernel", 420)
    if kernel == "pallas":
        from coreth_tpu.ops.keccak_pallas import staged_seg_impl

        seg = staged_seg_impl()
    else:
        from coreth_tpu.ops.keccak_staged import _segment_keccak

        seg = _segment_keccak
    lanes = int(os.environ.get("CORETH_TPU_BENCH_KERNEL_LANES", "8192"))
    words = jax.device_put(np.random.default_rng(1).integers(
        0, 2**32, size=(lanes, 1, 34), dtype=np.uint32))
    f = jax.jit(seg)
    f(words).block_until_ready()  # compile
    reps = 16
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        outs = [f(words) for _ in range(reps)]
        jax.block_until_ready(outs)
        best = min(best, time.perf_counter() - t0)
    hashes = lanes * reps / best
    REPORT["kernel_lanes"] = lanes
    REPORT["kernel_hashes_per_sec"] = round(hashes, 1)
    REPORT["kernel_mb_per_sec"] = round(hashes * 136 / 1e6, 1)


def run_resident(wd, planned_kernel="xla"):
    """Steady-state device-resident commits on a large warm trie.

    The device loop is PIPELINED: each round applies updates, plans, and
    dispatches without synchronizing; every root is verified against the
    host oracle after the loop. Steady-state throughput is therefore
    nodes/max(plan, transfer+kernel) — the deferred-absorb design goal.
    h2d bytes are measured exactly (the executor counts every upload)."""
    import numpy as np

    from coreth_tpu.native.mpt import IncrementalTrie
    from coreth_tpu.ops.keccak_resident import ResidentExecutor

    wd.arm("resident-build", 300)
    rng, items, keys, n, churn, rounds, threads = _inc_items()
    cpu_tree = IncrementalTrie(items)
    dev_tree = IncrementalTrie(items)
    seg_impl = None
    if planned_kernel == "pallas":
        from coreth_tpu.ops.keccak_pallas import staged_seg_impl

        seg_impl = staged_seg_impl()
    ex = ResidentExecutor(seg_impl=seg_impl)
    out = {"res_leaves": n, "res_churn": churn, "res_rounds": rounds}

    # initial commits (cold: compiles + full-trie upload)
    wd.arm("resident-warmup", 900)
    t0 = time.perf_counter()
    r0 = ex.root_bytes(dev_tree.commit_resident(ex))
    out["res_initial_s"] = round(time.perf_counter() - t0, 3)
    out["res_initial_h2d_mb"] = round(ex.h2d_bytes / 1e6, 1)
    r0_cpu = cpu_tree.commit_cpu(threads=threads)
    assert r0 == r0_cpu, "resident initial root mismatch"

    # steady state: both legs process IDENTICAL batches END TO END
    # (update + commit both timed — update is real per-block work shared
    # by both designs); batch 0 is the untimed warmup where device-shape
    # compiles land. Pre-generated so batch construction isn't timed.
    batches = [
        [(keys[rng.randrange(n)], rng.randbytes(60)) for _ in range(churn)]
        for _ in range(rounds + 1)
    ]
    cpu_roots, cpu_t, dirty_total = [], 0.0, 0
    for rnd, batch in enumerate(batches):
        wd.arm(f"resident-cpu-{rnd}", 240)
        t0 = time.perf_counter()
        cpu_tree.update(batch)
        cpu_roots.append(cpu_tree.commit_cpu(threads=threads))
        dt = time.perf_counter() - t0
        if rnd > 0:
            cpu_t += dt
            dirty_total += cpu_tree.dirty_stats()[0]

    wd.arm("resident-shape-warm", 600)
    dev_tree.update(batches[0])
    rw = ex.root_bytes(dev_tree.commit_resident(ex))
    assert rw == cpu_roots[0], "resident warmup root mismatch"

    wd.arm("resident-measure", 600)
    handles, h2d_total = [], 0
    t_start = time.perf_counter()
    for batch in batches[1:]:
        dev_tree.update(batch)
        handles.append(dev_tree.commit_resident(ex))
        h2d_total += ex.h2d_bytes
    # single synchronization point: block on the last root. The time
    # spent blocked here is device work the host could NOT hide behind
    # planning — its complement is the pipeline's overlap fraction.
    t_sync = time.perf_counter()
    np.asarray(handles[-1])
    dev_t = time.perf_counter() - t_start
    blocked = time.perf_counter() - t_sync
    out["res_overlap_fraction"] = round(
        max(0.0, 1.0 - blocked / dev_t), 3) if dev_t > 0 else 0.0

    # verify every pipelined root against the host oracle
    wd.arm("resident-verify", 300)
    for rnd, handle in enumerate(handles):
        assert ex.root_bytes(handle) == cpu_roots[rnd + 1], \
            f"pipelined resident root mismatch (round {rnd})"

    out["res_dirty_nodes"] = dirty_total
    out["res_dispatches_per_commit"] = ex.last_dispatches
    out["res_transfers_per_commit"] = ex.last_transfers
    out["res_h2d_bytes_per_node"] = round(h2d_total / max(dirty_total, 1), 1)
    out["res_h2d_mb_per_commit"] = round(h2d_total / rounds / 1e6, 2)
    out["res_cpu_nodes_per_sec"] = round(dirty_total / cpu_t, 1)
    out["res_tpu_nodes_per_sec"] = round(dirty_total / dev_t, 1)
    out["res_vs_cpu"] = round(cpu_t / dev_t, 3)
    out["res_h2d_bytes_per_commit"] = int(h2d_total / rounds)

    # ----------------------------------------- template-residency leg
    # Same batches through commit_template: the device keeps the arenas
    # (resident-path h2d cost) while every commit's digests absorb into
    # the host cache (planned-path semantics: root()/export always
    # valid, takeover without a full rehash). The absorb is a sync, so
    # this leg is the SERIAL floor the pipelined leg above is measured
    # against.
    wd.arm("resident-template-build", 600)
    tmpl_tree = IncrementalTrie(items)
    ex_t = ResidentExecutor(seg_impl=seg_impl)
    wd.arm("resident-template-warmup", 900)
    rt = tmpl_tree.commit_template(ex_t)
    assert rt == r0_cpu, "template initial root mismatch"
    tmpl_tree.update(batches[0])
    assert tmpl_tree.commit_template(ex_t) == cpu_roots[0], \
        "template warmup root mismatch"
    wd.arm("resident-template-measure", 900)
    tmpl_t, tmpl_h2d = 0.0, 0
    for rnd, batch in enumerate(batches[1:]):
        t0 = time.perf_counter()
        tmpl_tree.update(batch)
        root = tmpl_tree.commit_template(ex_t)
        tmpl_t += time.perf_counter() - t0
        tmpl_h2d += ex_t.h2d_bytes
        assert root == cpu_roots[rnd + 1], \
            f"template root mismatch (round {rnd})"
    out["res_template_nodes_per_sec"] = round(dirty_total / tmpl_t, 1)
    out["res_template_vs_cpu"] = round(cpu_t / tmpl_t, 3)
    out["res_template_h2d_bytes_per_node"] = round(
        tmpl_h2d / max(dirty_total, 1), 1)
    out["res_template_h2d_bytes_per_commit"] = int(tmpl_h2d / rounds)
    return out



def _inc_items():
    """Env knobs + the deterministic leaf set (seed 7) shared by the
    incremental/resident legs. Returns
    (rng, items, keys, n, churn, rounds, threads)."""
    import random

    n = int(os.environ.get("CORETH_TPU_BENCH_INC_LEAVES", "1000000"))
    churn = int(os.environ.get("CORETH_TPU_BENCH_INC_CHURN", "50000"))
    rounds = int(os.environ.get("CORETH_TPU_BENCH_INC_ROUNDS", "4"))
    threads = int(os.environ.get("CORETH_TPU_BENCH_CPU_THREADS", "0")) or (
        os.cpu_count() or 1
    )
    rng = random.Random(7)
    items = sorted(
        {rng.randbytes(32): rng.randbytes(rng.randint(40, 90))
         for _ in range(n)}.items()
    )
    keys = [k for k, _ in items]
    return rng, items, keys, n, churn, rounds, threads


def build_inc_workload():
    """Shared setup for the incremental/resident legs: env knobs, the
    deterministic leaf set (seed 7), and a fresh CPU+device trie pair.
    Returns (rng, cpu_tree, dev_tree, keys, n, churn, rounds, threads)."""
    from coreth_tpu.native.mpt import IncrementalTrie

    rng, items, keys, n, churn, rounds, threads = _inc_items()
    cpu_tree = IncrementalTrie(items)
    dev_tree = IncrementalTrie(items)
    return rng, cpu_tree, dev_tree, keys, n, churn, rounds, threads


def run_incremental(wd, planned):
    """Repeated-churn commits on a large warm trie: CPU-incremental vs
    device-incremental, bit-exact roots every round."""
    wd.arm("incremental-build", 300)
    rng, cpu_tree, dev_tree, keys, n, churn, rounds, threads = \
        build_inc_workload()
    out = {"inc_leaves": n, "inc_churn": churn, "inc_rounds": rounds}

    # initial commits (cold; the device one also compiles the mini shapes)
    cpu_tree.commit_cpu(threads=threads)
    wd.arm("incremental-warmup", 900)
    r0d = dev_tree.commit_device(planned)
    assert r0d == cpu_tree.root(), "incremental initial root mismatch"

    cpu_t = dev_t = 0.0
    dirty_total = 0
    flat_total = 0
    for rnd in range(rounds):
        batch = [(keys[rng.randrange(n)], rng.randbytes(60))
                 for _ in range(churn)]
        cpu_tree.update(batch)
        dev_tree.update(batch)

        wd.arm(f"incremental-cpu-{rnd}", 240)
        t0 = time.perf_counter()
        root_cpu = cpu_tree.commit_cpu(threads=threads)
        cpu_t += time.perf_counter() - t0
        dirty, flat_b = cpu_tree.dirty_stats()
        dirty_total += dirty
        flat_total += flat_b

        wd.arm(f"incremental-dev-{rnd}", 420)
        t0 = time.perf_counter()
        root_dev = dev_tree.commit_device(planned)
        dev_t += time.perf_counter() - t0
        assert root_dev == root_cpu, f"incremental round {rnd} root mismatch"

    out["inc_dirty_nodes"] = dirty_total
    out["inc_h2d_mb_per_commit"] = round(flat_total / rounds / 1e6, 1)
    out["inc_cpu_nodes_per_sec"] = round(dirty_total / cpu_t, 1)
    out["inc_tpu_nodes_per_sec"] = round(dirty_total / dev_t, 1)
    out["inc_vs_cpu"] = round(cpu_t / dev_t, 3)
    return out


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 — the partial report still lands
        import traceback

        traceback.print_exc()
        emit(f"{type(e).__name__}: {e}", code=1)
