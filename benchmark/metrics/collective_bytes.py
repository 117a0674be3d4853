"""Payload bytes a step of the collectives that span all the cell's
chips, counted from the compiled step's HLO (it repeats exactly)."""

from benchmark import hlo


def applies(config, job) -> bool:
    return job["chips"] > 1


def read(obs):
    return hlo.collective_bytes(obs.hlo_text, obs.cell.chips)
