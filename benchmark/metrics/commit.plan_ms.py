"""State commit: host planning of the device commits, per window block:
the `resident/phase/plan`, `resident/phase/export` and
`planned/phase/plan` timers."""

TIMERS = ("resident/phase/plan", "resident/phase/export",
          "planned/phase/plan")


def read(run):
    return 1000 * run.per_block(sum(run.timers[t] for t in TIMERS))
