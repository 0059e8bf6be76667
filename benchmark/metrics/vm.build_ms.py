"""VM: mean host time of `vm.build_block()` per window block (the
harness's span; it holds the miner's execution and the state commit)."""


def read(run):
    return 1000 * run.per_block(sum(b["build_s"] for b in run.blocks))
