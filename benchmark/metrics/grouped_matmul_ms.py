"""Device milliseconds a step in the grouped-matmul kernels of the routed
experts, by name: the Mosaic calls whose instruction is named for the
Pallas kernel, ``gmm`` or ``tgmm`` — forward, recomputation, input and
weight gradients."""

from benchmark import modules


def applies(config, job) -> bool:
    return "grouped_matmul" in config["kernels"]


def read(obs):
    return modules.ms_per_step(obs, "kernel_s", "grouped_matmul")
