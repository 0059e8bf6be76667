"""Kernels: the least time the commit programs' keccak work could take
under the HBM bound (bytes from the segment shapes over the peak HBM
bandwidth of benchmark/peaks.json) over the programs' device time.

The work is read from the executors' arguments (`harness.KeccakWork`),
so this reading depends on the signatures of `ResidentExecutor.run` and
`PlannedCommit.run`; benchmark/kernels.py says how."""

from benchmark.kernels import commit_program_s, keccak_bytes


def read(run):
    secs = commit_program_s(run)
    if not secs or not run.keccak["lanes"]:
        return None
    least = keccak_bytes(run.keccak) / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / secs
