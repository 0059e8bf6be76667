"""Kernels: device time of the whole commit programs that run the keccak
segment kernels (`jit_fused`, `jit_run`), per window block, from the
profiler trace. See benchmark/kernels.py for what the time includes."""

from benchmark.kernels import commit_program_s


def read(run):
    secs = commit_program_s(run)
    if not secs:
        return None
    return 1000 * run.per_block(secs)
