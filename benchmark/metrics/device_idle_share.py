"""Share of the traced window in which no operation ran on the slowest
device: 1 - union of its operations' intervals over the window."""


def applies(config, job) -> bool:
    return True


def read(obs):
    return 100.0 * obs.trace["idle_share"] if obs.trace else None
