"""State commit: the time the commit programs spent in JAX tracing and
lowering, per window block: the `compile_trace` and `compile_lower`
phase timers of the resident and planned executors, from the window
blocks' flight records (build and insert)."""

from benchmark.flight import timer_s

KEYS = ("compile_trace", "compile_lower",
        "planned/compile_trace", "planned/compile_lower")


def read(run):
    secs = timer_s(run, KEYS)
    if secs is None:
        return None
    return 1000 * run.per_block(secs)
