"""VM: mean host time of verify, accept and the acceptor-queue drain per
window block (the harness's span)."""


def read(run):
    return 1000 * run.per_block(sum(b["accept_s"] for b in run.blocks))
