"""Kernels: the least time the commit programs' keccak work could take
under the HBM bound over the programs' device time, with the work read
from the program's own counters (`resident/keccak/*`,
`planned/keccak/*` in the window blocks' flight records) instead of
the executors' arguments. benchmark/kernels.py says what the bytes and
the time include."""

from benchmark.flight import counter_sum
from benchmark.kernels import commit_program_s, keccak_bytes


def read(run):
    secs = commit_program_s(run)
    lanes = counter_sum(run, ("resident/keccak/lanes",
                              "planned/keccak/lanes"))
    blocks = counter_sum(run, ("resident/keccak/rate_blocks",
                               "planned/keccak/rate_blocks"))
    if not secs or not lanes:
        return None
    least = (keccak_bytes({"lanes": lanes, "blocks": blocks})
             / run.peaks()["hbm_bytes_per_s"])
    return 100.0 * least / secs
