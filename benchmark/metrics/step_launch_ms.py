"""Median over the window of the program's ``train_step.launch`` span:
the call of the compiled executable, which returns once the runtime has
taken the program.  While the device is the bottleneck this is where the
host waits for a free slot in the runtime's queue; ``dispatch_ms`` is
this plus ``step_prepare_ms`` plus entering the step's mesh."""

from benchmark import program_spans as ps
from benchmark.metrics import median_ms


def applies(config, job) -> bool:
    return True


def read(obs):
    return median_ms(ps.seconds_in_window(obs, "train_step.launch"))
