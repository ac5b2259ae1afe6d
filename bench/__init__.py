"""The chip benchmark: one command runs one cell of BENCHMARK.json once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by its name:

    bench/configs/<config>.json      sizes as run, source and cuts
    bench/arch/<arch>.py             the configuration's "arch" ("dense"
                                     without one): ModelConfig, weights,
                                     reference, FLOPs
    bench/workloads/<cell>.json      configuration, driver, traffic, limits
    bench/drivers/<driver>.py        the window loop (train)
    bench/metrics/<metric>.py        one reader per per-layer metric
"""
