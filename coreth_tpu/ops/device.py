"""Device-hasher resolution + the device degradation ladder.

The reference engages its parallel hasher automatically from the hot path
(/root/reference/trie/trie.go:618-619: >=100 unhashed nodes -> 16
goroutines). The TPU-native equivalent: `get_batch_keccak("auto")` hands
the chain a batched device keccak (ops/keccak_jax.BatchedKeccak) that
Trie.hash() engages above trie/hasher.BATCH_THRESHOLD, with the recursive
C++-keccak hasher below it. "off" keeps everything on the CPU hasher.

Resolution is lazy and fails soft: when JAX/the device backend is
unavailable the chain runs CPU-only — hashing is bit-exact either way, so
this is purely a throughput decision. The failure is loud in diagnostics
(structured log + `ops/device/resolve_fail` counter + the cached error in
debug_metrics), just silent to the block pipeline.

The degradation ladder (the robustness layer): a device can hang or fail
mid-run, after resolution succeeded. `DeviceLadder` wraps every laddered device dispatch in a
watchdog with bounded retry/backoff, and on exhaustion demotes the whole
device seam to the host MID-RUN:

    healthy --(timeout / repeated errors)--> demoted
    demoted --(1 healthy background probe)--> probation
    probation --(promote_after consecutive healthy probes)--> healthy
    probation --(any failed probe)--> demoted

Demotion flips `PlannedModeKeccak.planned` (a dynamic property) to False,
which reroutes Trie.hash and StateDB.intermediate_root to their host
paths, and routes the plain-callable seam through the threaded native
batch keccak — roots stay bit-exact through every rung. Events fan out to
listeners (core/blockchain pipes them into the flight recorder) and the
`ops/device/demotions` / `ops/device/promotions` counters.
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional

from ..fault import Backoff, FailpointError, failpoint
from ..fault import register as _register_failpoint

_cached: dict = {}

# failpoint sites (fault/__init__.py registry; armed via
# CORETH_TPU_FAILPOINTS or debug_setFailpoint)
FP_RESOLVE = _register_failpoint(
    "ops/device/resolve", "during lazy device-keccak resolution")
FP_DISPATCH = _register_failpoint(
    "ops/device/dispatch",
    "inside every laddered device dispatch (runs on the watchdog worker "
    "thread, so `hang` exercises the deadline)")
FP_PROBE = _register_failpoint(
    "ops/device/probe", "inside the ladder's background health probe")


class DeviceDegradedError(RuntimeError):
    """A laddered device dispatch exhausted its watchdog/retry budget and
    the ladder demoted to host; callers fall back to the host path."""


class DeviceLadder:
    """Process-wide device health state machine (the device, like the
    cached keccak fn, is process-global). Chains configure it from
    CacheConfig at construction and subscribe for flight-recorder
    events; `coreth_tpu.fault`-driven chaos tests drive it directly."""

    HEALTHY = "healthy"
    DEMOTED = "demoted"
    PROBATION = "probation"

    PROBE_MSG = b"coreth-tpu device health probe"
    DEFAULT_PROBE_TIMEOUT = 5.0

    def __init__(self):
        self._lock = threading.Lock()
        self.state = self.HEALTHY  # guarded-by: _lock
        self.last_error: Optional[str] = None  # guarded-by: _lock
        # knobs (configure()): call_timeout None = watchdog off — dispatch
        # runs inline with zero extra threads, the seed behavior
        self.call_timeout: Optional[float] = None
        self.max_retries = 1
        self.retry_base = 0.05
        self.probe_interval = 5.0
        self.promote_after = 3
        self._healthy_probes = 0  # guarded-by: _lock
        self._listeners: List[Callable] = []  # guarded-by: _lock
        self._probe_gen = 0  # guarded-by: _lock; invalidates stale probes
        self._probe_wake = threading.Event()

    # ---- configuration / wiring -----------------------------------------

    def configure(self, call_timeout: Optional[float] = None,
                  max_retries: Optional[int] = None,
                  probe_interval: Optional[float] = None,
                  promote_after: Optional[int] = None) -> None:
        """Apply chain knobs (CacheConfig.device_*). 0 timeouts mean
        'off', matching the resident watchdog's convention."""
        with self._lock:
            if call_timeout is not None:
                self.call_timeout = call_timeout if call_timeout > 0 else None
            if max_retries is not None:
                self.max_retries = max(0, int(max_retries))
            if probe_interval is not None:
                self.probe_interval = float(probe_interval)
            if promote_after is not None:
                self.promote_after = max(1, int(promote_after))

    def add_listener(self, fn: Callable) -> None:
        """fn(kind, fields) on every ladder event: retry/demote/
        probation/promote. Exceptions are counted, never propagated."""
        with self._lock:
            self._listeners.append(fn)

    def remove_listener(self, fn: Callable) -> None:
        with self._lock:
            try:
                self._listeners.remove(fn)
            except ValueError:
                pass

    def _notify(self, kind: str, **fields) -> None:
        with self._lock:
            listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(kind, dict(fields))
            except Exception:
                from ..metrics import count_drop

                count_drop("ops/device/listener_error")

    # ---- state -----------------------------------------------------------

    @property
    def healthy(self) -> bool:
        return self.state == self.HEALTHY

    def status(self) -> dict:
        with self._lock:
            return {
                "state": self.state,
                "last_error": self.last_error,
                "healthy_probes": self._healthy_probes,
                "call_timeout": self.call_timeout,
                "max_retries": self.max_retries,
                "probe_interval": self.probe_interval,
                "promote_after": self.promote_after,
            }

    def reset(self) -> None:
        """Back to healthy with no listeners; retires any probe thread.
        Test isolation — the ladder is process-global."""
        with self._lock:
            self.state = self.HEALTHY
            self.last_error = None
            self._healthy_probes = 0
            self._listeners.clear()
            self._probe_gen += 1
            self._probe_wake.set()
            self._probe_wake = threading.Event()

    # ---- dispatch (the watchdogged device call) --------------------------

    def dispatch(self, fn: Callable, what: str, *args):
        """Run one device call under the ladder: per-call watchdog
        deadline (call_timeout), bounded retry with capped backoff for
        transient errors, demotion on exhaustion. Raises
        DeviceDegradedError after demoting; callers take the host path."""
        from ..metrics import default_registry

        def run():
            failpoint("ops/device/dispatch")
            return fn(*args)

        timeout = self.call_timeout
        attempts = self.max_retries + 1
        backoff = Backoff(base=self.retry_base, cap=2.0)
        last: Optional[BaseException] = None
        for attempt in range(attempts):
            try:
                if timeout is not None:
                    from ..native.mpt import _run_with_watchdog

                    return _run_with_watchdog(run, timeout, what)
                return run()
            except Exception as e:
                last = e
                default_registry.counter("ops/device/dispatch_errors").inc()
                if attempt + 1 < attempts:
                    self._notify("retry", what=what, attempt=attempt + 1,
                                 error=repr(e))
                    backoff.sleep()
        self.demote(f"{what}: {last!r}")
        raise DeviceDegradedError(
            f"{what} demoted to host after {attempts} attempt(s): {last!r}"
        ) from last

    # ---- demotion / probation / re-promotion -----------------------------

    def demote(self, why: str) -> None:
        """Device -> host, idempotent. Starts the background probe loop
        that earns the way back (probation -> re-promotion)."""
        from ..log import error, get_logger
        from ..metrics import default_registry

        with self._lock:
            if self.state != self.HEALTHY:
                self.last_error = why
                return
            self.state = self.DEMOTED
            self._healthy_probes = 0
            self.last_error = why
        default_registry.counter("ops/device/demotions").inc()
        error(get_logger("ops"),
              "device demoted to host: dispatches run CPU-side until "
              "background probes re-promote", why=why)
        self._notify("demote", why=why)
        self._start_probe_thread()

    def promote(self) -> None:
        from ..log import get_logger, info
        from ..metrics import default_registry

        with self._lock:
            if self.state == self.HEALTHY:
                return
            self.state = self.HEALTHY
            self._healthy_probes = 0
        default_registry.counter("ops/device/promotions").inc()
        info(get_logger("ops"), "device re-promoted after healthy probes")
        self._notify("promote")

    def _probe_fn(self) -> Optional[Callable]:
        return _cached.get("fn")

    def _start_probe_thread(self) -> None:
        with self._lock:
            if (self.probe_interval <= 0 or self.promote_after <= 0
                    or _cached.get("fn") is None):
                return  # no road back: stay demoted (or no device at all)
            self._probe_gen += 1
            gen = self._probe_gen
        threading.Thread(target=self._probe_loop, args=(gen,),
                         name="device-probe", daemon=True).start()

    def _probe_loop(self, gen: int) -> None:
        from ..metrics import default_registry
        from ..native import keccak256 as _host_keccak
        from ..native.mpt import _run_with_watchdog

        expected = _host_keccak(self.PROBE_MSG)
        while True:
            with self._lock:
                if gen != self._probe_gen or self.state == self.HEALTHY:
                    return
                wake = self._probe_wake
                interval = self.probe_interval
                timeout = self.call_timeout or self.DEFAULT_PROBE_TIMEOUT
                fn = _cached.get("fn")
            wake.wait(interval)
            with self._lock:
                if gen != self._probe_gen or self.state == self.HEALTHY:
                    return
            if fn is None:
                return

            def probe():
                failpoint("ops/device/probe")
                return fn([self.PROBE_MSG])

            try:
                out = _run_with_watchdog(probe, timeout, "device health probe")
                ok = bool(out) and bytes(out[0]) == expected
            except Exception:
                default_registry.counter("ops/device/probe_errors").inc()
                ok = False
            self._on_probe(ok)

    def _on_probe(self, ok: bool) -> None:
        with self._lock:
            if ok:
                self._healthy_probes += 1
                entered_probation = self.state == self.DEMOTED
                if entered_probation:
                    self.state = self.PROBATION
                promote = self._healthy_probes >= self.promote_after
                probes = self._healthy_probes
            else:
                self._healthy_probes = 0
                relapsed = self.state == self.PROBATION
                if relapsed:
                    self.state = self.DEMOTED
                entered_probation = promote = False
        if ok and entered_probation:
            self._notify("probation", healthy_probes=probes)
        if ok and promote:
            self.promote()


_ladder = DeviceLadder()


def default_ladder() -> DeviceLadder:
    """The process-wide ladder every laddered seam shares."""
    return _ladder


def resolution_error() -> Optional[str]:
    """The cached device-resolution failure, if any (debug_metrics)."""
    e = _cached.get("error")
    return repr(e) if e is not None else None


def _host_batch_keccak(msgs) -> List[bytes]:
    """Bit-exact host fallback for a demoted device seam: the threaded
    native C++ batch keccak (same engine as trie/hasher.cpu_batch_keccak,
    minus the double-count of the batch counters — the marker wrappers
    already counted the batch)."""
    from ..native import default_cpu_threads, keccak256_batch

    return keccak256_batch(msgs, threads=default_cpu_threads())


def get_batch_keccak(mode: str = "auto") -> Optional[Callable]:
    """Resolve a `list[bytes] -> list[bytes32]` batched keccak, or None.

    mode: "auto"    — the planned u32 executor when the backend resolves
                      (same as "planned"), silent CPU fallback otherwise
          "planned" — the production fast path: Trie.hash/StateDB commits
                      drain through trie/planned.PlannedGraphBuilder ->
                      ops/keccak_planned.PlannedCommit — ONE bulk u32
                      transfer per commit, child digests AND storage roots
                      patched on device in word space, zero byte-level ops
                      on device. Fails loudly when forced.
          "batched" — level-batched hashing (one dispatch per trie level);
                      unavailability is an error: the operator forced the
                      device path, so degrading quietly would hide a
                      node-wide throughput regression
          "fused"   — single-dispatch commits: Trie.hash ships the whole
                      dirty set in ONE transfer with on-device digest
                      patching (trie/hasher.FusedHasher). Superseded by
                      "planned" (its on-device uint8 unpacking costs ~100x
                      the hashing, PERF.md); kept for A/B comparison and
                      NOT laddered — wrapping it would change what the A/B
                      measures.

          "off"     — None (CPU recursive hasher everywhere)

    Every returned callable except "fused" routes through the process
    DeviceLadder: healthy calls dispatch to the device (watchdogged when
    a deadline is configured), demoted calls run the bit-exact native
    host batch keccak.
    """
    if mode == "off":
        return None
    if mode not in ("auto", "planned", "batched", "fused"):
        raise ValueError(f"unknown device-hasher mode {mode!r}")
    if "fn" not in _cached:
        try:
            failpoint("ops/device/resolve")
            from ..utils import enable_compilation_cache

            enable_compilation_cache()
            from .keccak_jax import BatchedKeccak

            _cached["fn"] = BatchedKeccak().digests
        except Exception as e:  # fail-soft is only legal for "auto"
            from ..log import get_logger, warn
            from ..metrics import default_registry

            default_registry.counter("ops/device/resolve_fail").inc()
            warn(get_logger("ops"),
                 "device keccak unavailable, chain runs CPU-only",
                 error=repr(e),
                 failpoint=isinstance(e, FailpointError))
            _cached["fn"] = None
            _cached["error"] = e
    if _cached["fn"] is None and mode in ("planned", "batched", "fused"):
        raise RuntimeError(
            f"device-hasher forced to {mode!r} but the device keccak failed "
            f"to resolve: {_cached.get('error')!r}"
        )
    if _cached["fn"] is None:
        return None
    if mode == "fused":
        return FusedModeKeccak(_cached["fn"])
    if mode in ("auto", "planned"):
        return PlannedModeKeccak(_cached["fn"])
    return LadderedKeccak(_cached["fn"])


class LadderedKeccak:
    """Plain batch-keccak seam behind the degradation ladder: dispatches
    to the device while the ladder is healthy, runs the bit-exact native
    host batch when demoted (mid-call demotion included)."""

    def __init__(self, digests, ladder: Optional[DeviceLadder] = None):
        self._digests = digests
        self._ladder = ladder if ladder is not None else _ladder

    def __call__(self, msgs):
        from ..trie.hasher import count_keccak_batch

        count_keccak_batch(len(msgs))
        lad = self._ladder
        if not lad.healthy:
            return _host_batch_keccak(msgs)
        try:
            return lad.dispatch(self._digests, "device batch keccak", msgs)
        except DeviceDegradedError:
            return _host_batch_keccak(msgs)


class PlannedModeKeccak(LadderedKeccak):
    """Marker wrapper telling Trie.hash / StateDB.intermediate_root to take
    the planned u32 executor path; still callable as a plain batch keccak
    so every other consumer of the seam (proof verification, precompile)
    works unchanged.

    `planned` is a dynamic property, not a class attribute: while the
    ladder is demoted it reads False, which flips both consumers
    (trie/trie.py Trie.hash, state/statedb.py intermediate_root — they
    getattr the marker per call) to their host paths mid-run. Host and
    device hashing are bit-exact, so the only observable change is where
    the keccak runs."""

    @property
    def planned(self) -> bool:
        return self._ladder.healthy


class FusedModeKeccak:
    """Marker wrapper telling Trie.hash to take the single-dispatch
    FusedHasher path; still callable as a plain batch keccak so every
    other consumer of the seam (proof verification, precompile) works
    unchanged. Kept OFF the ladder: the mode exists for A/B comparison
    against "planned", and laddering it would change the measurement."""

    fused = True

    def __init__(self, digests):
        self._digests = digests

    def __call__(self, msgs):
        from ..trie.hasher import count_keccak_batch

        count_keccak_batch(len(msgs))
        return self._digests(msgs)
