"""Chip benchmark of the HyperParallel-JAX repository.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chips of
the machine it starts on and prints one JSON result line.  Everything a
cell uses is found by name under this directory:

  configs/<config>.json    model sizes as run, with source and departures
  traffic/<mix>.json       a mix's parameters; its ``kind`` names the
                           generator that reads them
  cells/<cell>.json        the cell's driver, its deployment's values and
                           the limits of the correctness comparison
  drivers/<driver>.py      builds the system under test and runs a cell
  generators/<kind>.py     makes a mix's work from the seed and offers it
  end_to_end/<metric>.py   one reader per end-to-end metric
  metrics/<metric>.py      one reader per per-layer metric

The yardstick lives here too: the generator, the float32 reference
(reference.py), the peaks table (peaks.py), the operation and byte counts
(costs.py) and the reduction of a profiler trace (trace.py).
"""
