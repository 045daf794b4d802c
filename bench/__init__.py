"""Chip benchmark of the ThemisIO engine: one configuration under one
traffic mix per cell, found by name from ``BENCHMARK.json``.

Run one cell with ``python3 -m bench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root, on a machine that
holds a TPU.  Everything the benchmark measures with lives here, apart from
the system under test (``src/repro``): the job-population generator, the
plain reference simulator that decides ``correct``, the trace reduction,
the device peaks and the kernel's work count.
"""
