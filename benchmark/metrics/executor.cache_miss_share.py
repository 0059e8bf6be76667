"""Device executors: share of resident commits in the window whose
compiled program was not in the executor's plan cache."""


def read(run):
    miss = run.counters["resident/plan_cache/misses"]
    total = miss + run.counters["resident/plan_cache/hits"]
    if not total:
        return None
    return 100.0 * miss / total
