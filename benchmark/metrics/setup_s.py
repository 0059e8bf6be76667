"""Set-up: process start to the first timed block (genesis, the mirror
boot, signing ahead, the warm-up blocks and their compiles)."""


def read(run):
    return run.setup_s
