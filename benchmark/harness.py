"""One run of one cell: the C-Chain VM driven as the Snowman engine would.

Everything a cell needs is found by name from BENCHMARK.json: the
configuration file, the traffic mix `benchmark/traffic/<traffic>.json`
and one reader `benchmark/metrics/<metric>.py` per metric.

A run:
  1. starts the signing workers (before JAX loads), draws the genesis
     from the configuration and the seed, and boots a `VM` over it;
  2. plays the warm-up blocks (the same for every seed), then turns the
     persistent compile cache off: a deployed node never meets a block's
     commit program twice, so a rerun of a seed must not read faster;
  3. closes the loop for `seconds`: before each build the tx pool holds
     at least one full block of signed txs; each block goes
     `build_block` -> `verify` -> `accept` -> `drain_acceptor_queue`, and
     the window ends at the accept of the block in flight;
  4. reads the device's peak memory, frees the program, and hands every
     accepted block to the reference (`reference.py`).
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import signer, state, traffic
from .reference import Reference, ReferenceError_, compare
from .trace_reduce import (SPAN_ACCEPT, SPAN_BUILD, SPAN_TOP_UP, SPAN_WINDOW,
                           union)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

# counters that move when a commit leaves the device without failing the
# call (the bring-up smoke's list): each must read 0 over the run
FALLBACK_COUNTERS = (
    "ops/device/resolve_fail",
    "ops/device/demotions",
    "state/resident/device_takeovers",
    "state/resident/cpu_fastpath",
    "state/resident/mesh_demotions",
    "chain/mirror/quarantines",
    "trie/planned/too_many_segments",
)
SEGMENT_COUNTERS = (
    "resident/segments/pallas",
    "resident/segments/xla",
    "planned/segments/pallas",
    "planned/segments/xla",
)
WINDOW_COUNTERS = SEGMENT_COUNTERS + (
    "resident/plan_cache/misses",
    "resident/plan_cache/hits",
    "resident/h2d_bytes",
)
WINDOW_TIMERS = (
    "resident/phase/plan",
    "resident/phase/export",
    "planned/phase/plan",
)
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class SetupError(Exception):
    """The cell cannot run here; no result is printed."""


def process_age() -> float:
    """Seconds since this process started, by the kernel's clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# ---- the cell, resolved by name ------------------------------------------

class Cell:
    def __init__(self, root: str, workload: str):
        self.root = root
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            self.bench = json.load(f)
        found = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not found:
            raise SetupError(f"no workload {workload!r} in {path}")
        self.workload = found[0]
        entry = [c for c in self.bench["configs"]
                 if c["name"] == self.workload["config"]][0]
        self.chips = int(self.workload["chips"])
        self.config = self._json(entry["file"])
        self.mix = self._json(os.path.join(
            "benchmark", "traffic", self.workload["traffic"] + ".json"))

    def _json(self, rel: str) -> dict:
        with open(os.path.join(self.root, rel)) as f:
            return json.load(f)

    def metrics(self, kind: str) -> list:
        """This cell's entries of `end_to_end` or `per_layer`."""
        name = self.workload["name"]
        return [m for m in self.bench[kind]
                if name in m.get("workloads", [name])]

    def reader(self, metric: str):
        path = os.path.join(self.root, "benchmark", "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


# ---- what readers read ---------------------------------------------------

class RunRecord:
    """Everything the window produced, for the metric readers."""

    def __init__(self):
        self.blocks: list = []       # one dict per window block
        self.window_s = 0.0
        self.setup_s = 0.0
        self.counters: dict = {}     # deltas over the window
        self.timers: dict = {}       # seconds, deltas over the window
        self.flight: list = []       # flight records of window blocks
        self.keccak = {"lanes": 0, "blocks": 0}  # device work, window
        self.trace = None            # trace_reduce.Summary, --trace 1
        self.compile_s = 0.0         # JAX compile seconds in the window
        self.cache_hits = 0          # persistent-cache hits in the window
        self.failure = ""            # the exception that ended the window
        self.device_kind = ""
        self.peaks_table: dict = {}

    @property
    def n_blocks(self) -> int:
        return len(self.blocks)

    def per_block(self, total: float) -> float:
        return total / self.n_blocks

    def peaks(self) -> dict:
        if self.device_kind not in self.peaks_table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           "in benchmark/peaks.json")
        return self.peaks_table[self.device_kind]


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank: the smallest value with at least a
    share q of the values at or below it."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def gas_rate(blocks, window_s: float) -> float:
    """Mgas/s: the gas of every window block over the whole window."""
    return sum(b["gas_used"] for b in blocks) / window_s / 1e6


# ---- program-side instruments (around calls into the program) ------------

class _Compiles:
    """JAX's compile events (trace, lowering, backend compile) as time
    spans, and persistent-cache hits and misses, process-wide (monitoring
    listeners cannot be removed, so one set is registered). Compile time
    over a period is the union of the spans inside it: the events nest
    (a jitted function traced inside another reports its own span) and
    run on several threads at once, so their durations do not add."""
    spans: list = []  # (start, end), time.time() clock, in end order
    cache_hits = 0
    cache_misses = 0
    _on = False

    @classmethod
    def install(cls) -> None:
        if cls._on:
            return
        import jax

        def on_span(name, start, end, **_kw):
            if name in COMPILE_EVENTS:
                cls.spans.append((start, end))

        def on_event(name, **_kw):
            if name == CACHE_HIT_EVENT:
                cls.cache_hits += 1
            elif name == CACHE_MISS_EVENT:
                cls.cache_misses += 1

        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)
        cls._on = True

    @classmethod
    def seconds(cls, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] (time.time()) in which a compile ran."""
        return sum(e - s for s, e in union(
            (max(s, t0), min(e, t1)) for s, e in cls.spans
            if e > t0 and s < t1))


class KeccakWork:
    """Counts the keccak work each device commit hands its kernels: lanes
    and keccak-f[1600] blocks absorbed, read from the segment specs the
    resident and planned executors receive. The program has no counter of
    this work, so this wraps the executors' `run` and depends on their
    signatures (benchmark/kernels.py)."""

    def __init__(self):
        self.lanes = 0
        self.blocks = 0
        self._saved = []

    def __enter__(self):
        from coreth_tpu.ops.keccak_planned import PlannedCommit
        from coreth_tpu.ops.keccak_resident import ResidentExecutor

        work = self
        res_run, plan_run = ResidentExecutor.run, PlannedCommit.run

        def resident(ex, export):
            for s in export["specs"]:
                work.blocks += int(s[0]) * int(s[1])
                work.lanes += int(s[1])
            return res_run(ex, export)

        def planned(pc, specs, *a, **kw):
            for s in specs:
                work.blocks += s.blocks * s.lanes
                work.lanes += s.lanes
            return plan_run(pc, specs, *a, **kw)

        self._saved = [(ResidentExecutor, "run", res_run),
                       (PlannedCommit, "run", plan_run)]
        ResidentExecutor.run = resident
        PlannedCommit.run = planned
        return self

    def __exit__(self, *exc):
        for cls, name, fn in self._saved:
            setattr(cls, name, fn)


def _counters(names) -> dict:
    from coreth_tpu.metrics import default_registry

    return {n: default_registry.counter(n).count() for n in names}


def _timers(names) -> dict:
    from coreth_tpu.metrics import default_registry

    return {n: default_registry.timer(n).total() for n in names}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _set_compile_cache(on: bool) -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", on)
    cc.reset_cache()


# ---- the run -------------------------------------------------------------

def _new_vm(genesis, cfg: dict):
    from coreth_tpu import params
    from coreth_tpu.core.genesis import Genesis, GenesisAccount
    from coreth_tpu.ethdb import MemoryDB
    from coreth_tpu.vm.shared_memory import Memory
    from coreth_tpu.vm.vm import VM, SnowContext, VMConfig

    alloc = {a: GenesisAccount(balance=b) for a, b in genesis.alloc.items()}
    g = Genesis(config=params.TEST_CHAIN_CONFIG, gas_limit=cfg["gas_limit"],
                alloc=alloc)
    vm = VM()
    interval = cfg["block_interval_s"]

    def tick():
        return vm.blockchain.current_block.time + interval

    vm.initialize(SnowContext(shared_memory=Memory()), MemoryDB(), g,
                  VMConfig(clock=tick),
                  config_bytes=json.dumps(cfg["vm_config"]).encode())
    return vm


def _device():
    import jax

    dev = jax.devices()[0]
    return dev, len(jax.devices())


class Session:
    """A booted node and its traffic: set-up in the constructor, then any
    number of windows, then `close` and `verify`. A run is one window;
    the control script reads several seeds from one set-up."""

    def __init__(self, cell: Cell, seed: int, require_tpu: bool = True,
                 err=None):
        self.cell, self.seed, self.err = cell, seed, err or sys.stderr
        cfg, mix = cell.config, cell.mix
        # where set-up goes: wall seconds of each phase, and for those
        # that touch the program its JAX compile seconds and cache hits
        self.phases = {"process_start": {"wall_s": process_age()}}
        t0 = time.perf_counter()
        self.genesis = state.Genesis(cfg, seed)
        self.phases["genesis_draw"] = {"wall_s": time.perf_counter() - t0}
        self.stream = traffic.TxStream(self.genesis, mix, mix["warm_seed"])
        self.pool = signer.SignerPool(self.genesis.keys, cfg["chain_id"],
                                      mix["sign_workers"])
        self.vm = None
        self.cache_was_on = None     # the compile cache before the window
        try:
            self._boot(require_tpu)
        except BaseException:
            self.close()
            raise

    def _boot(self, require_tpu: bool) -> None:
        cell, mix = self.cell, self.cell.mix
        self.chunks: list = []       # item lists submitted, not yet issued
        for _ in range(mix["warm_blocks"]):
            self._submit()
        # the window's first blocks are signed while the node boots
        self.stream.reseed(self.seed)
        for _ in range(mix["sign_ahead_blocks"]):
            self._submit()
        t0 = time.perf_counter()
        if require_tpu:
            check_accelerator(cell.chips)
        self.dev, self.count = _device()
        with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
            self.peaks = json.load(f)["devices"]
        if require_tpu and self.dev.device_kind not in self.peaks:
            raise SetupError(f"device kind {self.dev.device_kind!r} is not "
                             "in benchmark/peaks.json")
        _Compiles.install()
        self.phases["jax_start"] = {"wall_s": time.perf_counter() - t0}
        self.fallback0 = _counters(FALLBACK_COUNTERS)
        self.signed_by: dict = {}    # raw tx -> sender, for the reference
        self.accepted: list = []     # block bytes, warm-up blocks included
        self.issued = self.included = self.rejected = 0
        self.host_mode_blocks = 0
        self.memory_peak = 0
        with self._phase("vm_initialize"):
            self.vm = _new_vm(self.genesis, cell.config)
        self.chain = self.vm.blockchain
        self.genesis_root = self.chain.genesis_block.root
        from coreth_tpu.core.state_manager import ResidentTrieWriter

        mirror = self.chain.state_database.mirror
        self.resident = (isinstance(self.chain.trie_writer, ResidentTrieWriter)
                         and mirror is not None and mirror.host_mode is False)
        # the warm-up blocks' traffic is the same for every seed, so after
        # a checkout's first run their programs come from the cache
        self.failure = ""  # a block the program failed to build or accept
        with self._phase("warm_blocks"):
            try:
                for _ in range(mix["warm_blocks"]):
                    self._drive_block(mix["txs_per_block"], None)
            except Exception as e:  # noqa: BLE001 - a failed block, reported
                self._failed(e)

    @contextlib.contextmanager
    def _phase(self, name: str):
        h0, m0 = _Compiles.cache_hits, _Compiles.cache_misses
        t0 = time.time()
        yield
        t1 = time.time()
        self.phases[name] = {"wall_s": t1 - t0,
                             "compile_s": _Compiles.seconds(t0, t1),
                             "cache_hits": _Compiles.cache_hits - h0,
                             "cache_misses": _Compiles.cache_misses - m0}

    def _failed(self, e: Exception) -> None:
        import traceback

        self.failure = f"{type(e).__name__}: {e}"
        traceback.print_exc(file=self.err)

    def reseed(self, seed: int) -> None:
        """Draw the traffic signed from now on from another seed."""
        self.seed = seed
        self.stream.reseed(seed)

    def _submit(self) -> None:
        items = self.stream.block()
        self.pool.submit(items)
        self.chunks.append(items)

    def _top_up(self, target: int) -> None:
        from coreth_tpu.core.types import Transaction

        while self.issued - self.included < target:
            items = self.chunks.pop(0)
            for item, raw in zip(items, self.pool.take()):
                self.signed_by[raw] = self.genesis.senders[item[0]]
                try:
                    self.vm.issue_tx(Transaction.decode(raw))
                except Exception as e:  # noqa: BLE001 - counted, reported
                    self.rejected += 1
                    print(f"tx rejected by the pool: {e}", file=self.err)
                self.issued += 1
            self._submit()

    def _drive_block(self, target: int, rec) -> dict:
        import jax

        chain = self.chain
        with jax.profiler.TraceAnnotation(SPAN_TOP_UP):
            self._top_up(target)
        t0 = time.time()
        with jax.profiler.TraceAnnotation(SPAN_BUILD):
            blk = self.vm.build_block()
        t1 = time.time()
        with jax.profiler.TraceAnnotation(SPAN_ACCEPT):
            blk.verify()
            blk.accept()
            chain.drain_acceptor_queue()
        t2 = time.time()
        hdr = blk.eth_block.header
        self.included += len(blk.eth_block.transactions)
        self.accepted.append(blk.bytes())
        mirror = chain.state_database.mirror
        self.host_mode_blocks += bool(mirror is not None and mirror.host_mode)
        b = {"number": hdr.number, "txs": len(blk.eth_block.transactions),
             "gas_used": hdr.gas_used, "gas_limit": hdr.gas_limit,
             "build_s": t1 - t0, "accept_s": t2 - t1, "block_s": t2 - t0,
             "compile_s": _Compiles.seconds(t0, t2)}
        if rec is not None:
            rec.blocks.append(b)
            flight = chain.flight_recorder.find(blk.id())
            if flight is not None:
                rec.flight.append(flight)
        return b

    def window(self, seconds: float, trace: bool = False,
               trace_dir: str | None = None) -> RunRecord:
        """Closed-loop blocks for `seconds`; the block in flight when they
        elapse is finished and counted."""
        import jax

        mix = self.cell.mix
        rec = RunRecord()
        rec.device_kind = self.dev.device_kind
        rec.peaks_table = self.peaks
        target = mix["pool_blocks"] * mix["txs_per_block"]
        # A deployed node never meets a block's commit program twice: with
        # the cache on, a rerun of a seed would read its blocks' programs
        # from disk. So the window compiles; `close` restores the cache.
        if self.cache_was_on is None:
            self.cache_was_on = jax.config.jax_enable_compilation_cache
            _set_compile_cache(False)
        c_before, t_before = _counters(WINDOW_COUNTERS), \
            _timers(WINDOW_TIMERS)
        hits0 = _Compiles.cache_hits
        tmp_trace = None
        if trace:
            tmp_trace = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            # no Python tracer: it writes an event per Python call, which
            # made a one-block trace 168 MB and slow to read back
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp_trace, profiler_options=opts)
        try:
            with KeccakWork() as work:
                rec.setup_s = process_age()
                w0 = time.time()
                with jax.profiler.TraceAnnotation(SPAN_WINDOW):
                    try:
                        while not self.failure:
                            self._drive_block(target, rec)
                            if time.time() - w0 >= seconds:
                                break
                    except Exception as e:  # noqa: BLE001 - a failed block
                        self._failed(e)
                w1 = time.time()
                rec.window_s = w1 - w0
                rec.failure = self.failure
        finally:
            if trace:
                jax.profiler.stop_trace()
        rec.keccak = {"lanes": work.lanes, "blocks": work.blocks}
        rec.counters = _delta(_counters(WINDOW_COUNTERS), c_before)
        rec.timers = _delta(_timers(WINDOW_TIMERS), t_before)
        rec.compile_s = _Compiles.seconds(w0, w1)
        rec.cache_hits = _Compiles.cache_hits - hits0
        stats = self.dev.memory_stats() or {}
        self.memory_peak = int(stats.get("peak_bytes_in_use", 0))
        if trace:
            from .trace_reduce import reduce_dir

            rec.trace = reduce_dir(tmp_trace)
            if trace_dir is None:
                shutil.rmtree(tmp_trace, ignore_errors=True)
        return rec

    def close(self) -> None:
        """Stop the node and the signers and free the device state."""
        if self.vm is not None:
            self.vm.shutdown()
            self.vm = self.chain = None
            gc.collect()
        if self.cache_was_on is not None:
            _set_compile_cache(self.cache_was_on)
        self.pool.close()

    def verify(self, rec: RunRecord) -> dict:
        """Every number compared, with its limit: the reference's replay
        of every accepted block, and the guarantees the run keeps."""
        checks = reference_checks(self.genesis, self.accepted,
                                  self.signed_by, self.genesis_root)
        checks.update(self.guarantee_checks(rec))
        return checks

    def guarantee_checks(self, rec: RunRecord) -> dict:
        """The device path carried the window, and nothing fell back."""
        moved = _delta(_counters(FALLBACK_COUNTERS), self.fallback0)
        segs = sum(rec.counters[c] for c in SEGMENT_COUNTERS)
        return {
            "fallback_moves": {"value": sum(moved.values()), "max": 0},
            "host_mode_blocks": {"value": self.host_mode_blocks, "max": 0},
            "resident_mirror_on_device": {"value": int(self.resident),
                                          "min": 1},
            "window_device_segments": {"value": segs, "min": 1},
            "txs_rejected": {"value": self.rejected, "max": 0},
            "failed_blocks": {"value": int(bool(rec.failure)), "max": 0},
        }


def check_accelerator(chips: int) -> None:
    dev, count = _device()
    if dev.platform != "tpu":
        raise SetupError(f"JAX finds no TPU (platform {dev.platform!r})")
    if count < chips:
        raise SetupError(f"the cell asks for {chips} chips, JAX sees {count}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             trace_dir: str | None = None, out=None, err=None) -> dict:
    """Run one cell and print its result line; returns the result.

    require_tpu=False lets the tests drive the whole run on the CPU.
    trace_dir keeps the profiler trace there instead of deleting it."""
    out = out or sys.stdout
    err = err or sys.stderr
    cell = Cell(root, workload)
    session = Session(cell, seed, require_tpu, err)
    try:
        rec = session.window(seconds, trace, trace_dir)
    finally:
        session.close()
    checks = session.verify(rec)
    correct = all(within(c) for c in checks.values())
    result = _result(cell, rec, trace, correct, session, checks)
    mix = cell.mix
    short = sum(1 for b in rec.blocks
                if b["gas_used"] + mix["gas"] <= cell.config["gas_limit"])
    pool = session.pool
    print("setup " + json.dumps(session.phases), file=out, flush=True)
    print("supply " + json.dumps({
        "window_blocks": rec.n_blocks, "blocks_built_short": short,
        "signed_txs": pool.signed,
        "sign_tx_per_worker_s": pool.signed / pool.sign_s
        if pool.sign_s else 0.0,
        "sign_workers": mix["sign_workers"],
        "wait_for_signing_s": pool.wait_s,
        "window_compile_cache_hits": rec.cache_hits,
        "blocks_s_compile_s": [[b["block_s"], b["compile_s"]]
                               for b in rec.blocks]}), file=out, flush=True)
    print_checks(checks, err)
    print(json.dumps(result), file=out, flush=True)
    return result


def print_checks(checks: dict, err) -> None:
    for name, c in checks.items():
        bound = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name} {c['value']} limit {bound}", file=err)
    err.flush()


def within(c: dict) -> bool:
    if "max" in c:
        return c["value"] <= c["max"]
    return c["value"] >= c["min"]


def reference_checks(genesis, accepted, signed_by, genesis_root) -> dict:
    """Replay every accepted block through the reference."""
    results, error, ref_genesis = [], None, None
    try:
        ref = Reference(genesis)
        ref_genesis = ref.genesis_root
        for blob in accepted:
            results.append(ref.apply_block(blob, signed_by.get))
    except ReferenceError_ as e:
        error = str(e)
    diffs = compare(results)
    return {
        "genesis_root_diff": {"value": int(ref_genesis != genesis_root),
                              "max": 0},
        **{k: {"value": v, "max": 0} for k, v in diffs.items()},
        "blocks_not_replayed": {"value": len(accepted) - len(results)
                                + int(error is not None), "max": 0},
    }


def _result(cell, rec, trace, correct, session, checks) -> dict:
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    if rec.blocks and not rec.failure:
        for m in cell.metrics(kind):
            value = cell.reader(m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": session.dev.platform,
              "kind": session.dev.device_kind, "count": session.count,
              "memory_peak_bytes": session.memory_peak}
    failed = int(bool(rec.failure))
    result = {"correct": correct, "attempted": rec.n_blocks + failed,
              "failed": failed or int(not correct),
              "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": rec.trace.top_ops[:10],
                               "idle_gaps": rec.trace.idle_gaps[:10]}
    result["checks"] = checks
    return result
