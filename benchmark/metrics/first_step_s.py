"""Seconds of the first step: trace, compile or load from the cache, run
(host clock, fenced)."""


def applies(config, job) -> bool:
    return True


def read(obs):
    return obs.setup["first_step_s"]
