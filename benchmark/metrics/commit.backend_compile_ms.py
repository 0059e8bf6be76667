"""State commit: the time XLA's backend spent compiling the commit
programs, per window block: the `compile_backend` phase timers of the
resident and planned executors, from the window blocks' flight records
(build and insert)."""

from benchmark.flight import timer_s

KEYS = ("compile_backend", "planned/compile_backend")


def read(run):
    secs = timer_s(run, KEYS)
    if secs is None:
        return None
    return 1000 * run.per_block(secs)
