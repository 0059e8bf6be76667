"""The keccak work of the commit programs, from their shapes, and their
device time.

A segment of `lanes` trie nodes, each `blocks` keccak-f[1600] rate
blocks long, reads 136 bytes per block absorbed and writes a 32-byte
digest per lane. The program carries no counter of this work, so
`harness.KeccakWork` reads it from the segment specs handed to
`ResidentExecutor.run(export)` (`export["specs"]`: (blocks, lanes, ...)
tuples) and `PlannedCommit.run(specs, ...)` (`spec.blocks`,
`spec.lanes`). A change to those signatures has to change this reading.

The kernels carry no names of their own either, so the device time is
that of the whole programs that run them: the resident executor's
commit program (`fused`) and the planned executor's (`run`), their
scatters into the node arena included. The roofline share taken from it
is therefore a lower bound on the keccak kernels' own.
"""

from __future__ import annotations

RATE_BYTES = 136
DIGEST_BYTES = 32
COMMIT_PROGRAMS = ("jit_fused", "jit_run")


def keccak_bytes(work: dict) -> int:
    return RATE_BYTES * work["blocks"] + DIGEST_BYTES * work["lanes"]


def commit_program_s(run) -> float:
    if run.trace is None or not run.trace.devices:
        return 0.0
    return run.trace.module_seconds(COMMIT_PROGRAMS)
