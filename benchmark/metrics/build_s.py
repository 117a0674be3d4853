"""Seconds to make the model, its optimizer state and the cell's data on
the device (host clock, fenced), after the parity check and before the
first step."""


def applies(config, job) -> bool:
    return True


def read(obs):
    return obs.setup["build_s"]
