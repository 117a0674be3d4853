"""Device milliseconds a step in every operation that is neither a
collective nor a Mosaic kernel: the matmul and convolution fusions with
whatever XLA fused into them (the optimizer's update, BatchNorm's
reductions), elementwise work, copies.  One figure, because XLA decides
what shares a fusion: the split between matrix-unit and other work
moved by 30 ms between the one-chip and the dp=4 LM cells on identical
arithmetic (chip runs, PR 22).  ``breakdown`` names the operations."""

from benchmark.metrics import class_seconds, trace_ms_per_step


def applies(config, job) -> bool:
    return True


def read(obs):
    return trace_ms_per_step(obs, class_seconds(obs, "compute"))
