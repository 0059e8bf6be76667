"""What the program's flight records say about the window blocks.

`run.flight` holds the flight record of each window block (served by
`debug_blockFlightRecord`). A block the node built carries a `build`
section beside the insert's own fields; both hold `resident` (phase
timers, seconds) and `counters` (deltas) over separate work, so a
block's total is their sum. A program without these fields gives
nothing: each function then returns None, and so does its reader.
"""

from __future__ import annotations


def sections(run) -> list:
    """The insert record and build section of every window block."""
    out = []
    for rec in run.flight:
        out.append(rec)
        if isinstance(rec.get("build"), dict):
            out.append(rec["build"])
    return out


def timer_s(run, keys) -> float | None:
    """Seconds of the named `resident` timers over the window, or None
    where no record carries them."""
    found, total = False, 0.0
    for sec in sections(run):
        timers = sec.get("resident", {})
        for k in keys:
            if k in timers:
                found = True
                total += timers[k]
    return total if found else None


def counter_sum(run, names) -> int | None:
    """The named counters summed over the window, or None where no
    record carries them."""
    found, total = False, 0
    for sec in sections(run):
        counters = sec.get("counters", {})
        for n in names:
            if n in counters:
                found = True
                total += counters[n]
    return total if found else None
