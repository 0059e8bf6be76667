"""Chain: the flight recorder's `execute` phase (the state processor
running the block's transactions at insert), mean per window block."""


def read(run):
    secs = [r["phases"]["execute"] for r in run.flight
            if "execute" in r.get("phases", {})]
    if not secs:
        return None
    return 1000 * sum(secs) / len(secs)
