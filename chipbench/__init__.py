"""chipbench: the chip benchmark of the serving path.

One run serves one cell (a model configuration under a traffic mix) through
``repro.launch.serve.ServeEngine`` on the chip, and prints one JSON line.
Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is a file of its own, found by the name in ``BENCHMARK.json``:

    configs/<config>.json    sizes, dtype, source, cut and deployment
    traffic/<mix>.json       lengths, arrivals and load of a mix
    cells/<cell>.json        slots, the correctness sample and its limits
    metrics/<metric>.py      one reader per per-layer metric
    reference/<family>.py    weights from the seed and a float32 forward
    work/<family>.py         operations and bytes the work needs
"""
