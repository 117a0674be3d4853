"""Share of its roofline the flash kernels reach: the least time the chip
could take for a step's flash work (``flops.flash_step_cost`` against the
chip's peaks; at head_dim 128 and seq 1024 the compute bound binds),
over the device time of the Mosaic calls."""

from benchmark import flops
from benchmark.metrics import class_seconds


def applies(config, job) -> bool:
    return "flash" in config["kernels"]


def read(obs):
    seconds = class_seconds(obs, "mosaic")
    if not seconds or not obs.peaks:
        return None
    cost = obs.built.kernel_cost["flash"]
    least, _bound = flops.roofline_s(cost["flops"], cost["bytes"], obs.peaks)
    return 100.0 * least * obs.traced_steps / seconds
