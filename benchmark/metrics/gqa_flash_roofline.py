"""Share of their roofline the flash kernels of a hybrid model's
attention reach: ``flops.flash_step_cost`` on the operand *after* the
key/value heads are repeated (what the kernel is given) against the
chip's peaks, over the device time of those calls alone."""

from benchmark import flops, modules


def applies(config, job) -> bool:
    return "gqa_flash" in config["kernels"]


def read(obs):
    ms = modules.ms_per_step(obs, "kernel_s", "gqa_flash")
    if not ms or not obs.peaks:
        return None
    cost = obs.built.kernel_cost["gqa_flash"]
    least, _bound = flops.roofline_s(cost["flops"], cost["bytes"], obs.peaks)
    return 100.0 * least / (ms / 1e3)
