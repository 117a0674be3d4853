"""Device milliseconds a step in the flash kernels of a hybrid model's
attention, by name: the Mosaic calls lowered from under an ``attn``
module and no other Mosaic call (``flash_ms`` reads every Mosaic call as
flash and so does not apply where a second kernel runs)."""

from benchmark import modules


def applies(config, job) -> bool:
    return "gqa_flash" in config["kernels"]


def read(obs):
    return modules.ms_per_step(obs, "kernel_s", "gqa_flash")
