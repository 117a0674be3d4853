"""The benchmark of horovod_tpu: cells, harness and yardstick.

``BENCHMARK.json`` at the root of the repo names the cells; everything a
cell runs lives here, found by name (``README.md`` beside this file).
From the program the benchmark takes only the system under test
(``hvd.init`` → ``DistributedTrainStep`` → ``data/``), never a metric.
"""
