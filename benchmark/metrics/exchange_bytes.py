"""Payload bytes a step of the collectives that span all the cell's
chips, each ``channel_id`` once (``benchmark/exchange.py``): what
crosses the wire.  ``collective_bytes`` counts a line an all-reduce, and
an async collective fusion clones the line into every step of the
chain."""

from benchmark import exchange


def applies(config, job) -> bool:
    return job["chips"] > 1


def read(obs):
    if not obs.hlo_text:
        return None
    return exchange.exchange_bytes(obs.hlo_text, obs.cell.chips)
