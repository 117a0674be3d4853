"""Device milliseconds a step in the Mosaic calls whose instruction is
named ``flash_fwd``: the flash forward and, where a block is
rematerialised, its second run.  With ``flash_bwd_ms`` it adds up to
``flash_ms`` (``gqa_flash_ms`` on a hybrid).  A step whose attention is
not the flash kernel holds no such call and reads as nothing."""

from benchmark import phases


def applies(config, job) -> bool:
    return bool({"flash", "gqa_flash"} & set(config["kernels"]))


def read(obs):
    return phases.ms_per_step(obs, "flash_fwd", "kernel_s")
