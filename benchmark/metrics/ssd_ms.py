"""Device milliseconds a step in the Mamba-2 scan alone: what runs under
``mamba/ssd`` (``benchmark/modules.py``) — the chunked recurrence as
``jax.numpy`` einsums or as Mosaic kernels, by the same rule — forward,
recomputation and backward.  The projections, the convolution and the
gated norm are ``ssm_ms``'s and not in it."""

from benchmark import modules


def applies(config, job) -> bool:
    return "M" in config.get("layers", "")


def read(obs):
    return modules.ms_per_step(obs, "module_s", "mamba/ssd")
