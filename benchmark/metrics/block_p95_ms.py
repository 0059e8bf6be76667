"""Block latency: nearest-rank 95th percentile over every window block,
from `build_block` to the end of accept and the acceptor-queue drain.
Under 20 blocks this is the slowest block."""

from benchmark.harness import nearest_rank


def read(run):
    return 1000 * nearest_rank([b["block_s"] for b in run.blocks], 0.95)
