"""Device milliseconds a step under the scope ``head`` of a language
model (``TransformerLM``, ``HybridLM``): the final norm, the logits'
matmul (tied or not) and their divisor, forward and backward — the
backward's operations keep the scope under ``transpose(...)`` — by
``benchmark/phases.py``'s rules.  The cross-entropy is the loss
function's (``loss`` / ``loss_fn`` in ``phases.account``).  A step that
lays no scope (a parent commit) reads as nothing."""

from benchmark import phases


def applies(config, job) -> bool:
    return "vocab_size" in config


def read(obs):
    return phases.ms_per_step(obs, "head")
