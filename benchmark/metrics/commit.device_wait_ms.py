"""Device executors: the time the resident commit path blocked on the
device (`resident/phase/wait`: the root's readback and the staging
ring's wait), per window block, from the window blocks' flight records
(build and insert)."""

from benchmark.flight import timer_s


def read(run):
    secs = timer_s(run, ("wait",))
    if secs is None:
        return None
    return 1000 * run.per_block(secs)
