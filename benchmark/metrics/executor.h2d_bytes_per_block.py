"""Device executors: bytes the resident executor uploaded to the device
(`resident/h2d_bytes`), per window block."""


def read(run):
    return run.per_block(run.counters["resident/h2d_bytes"])
