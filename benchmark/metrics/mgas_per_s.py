"""Throughput: the gas of every block accepted in the window (all of each
block started in it) over the whole window, in millions of gas a second."""

from benchmark.harness import gas_rate


def read(run):
    return gas_rate(run.blocks, run.window_s)
