"""Chip benchmark: one cell of ``BENCHMARK.json`` per run, on the TPU.

``python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once: it builds the cell's inputs and weights from the seed,
warms up every shape the cell uses, measures for ``--seconds``, checks the
timed path's output against a plain reference kept here, and prints one JSON
object as the last line of standard output.

Everything is found by name from ``BENCHMARK.json``: a configuration is
``configs/<config>.json`` (with its plain reference under ``reference/``), a
traffic mix is ``traffic/<traffic>.json`` (read by the one runner its
``kind`` names, under ``runners/``), and a per-layer metric is
``metrics/<metric>.py``.  The program under test is ``src/repro``: the
collective runner calls ``repro.core.schedules``, and the train runner drives
``repro.runtime.trainer.Trainer`` with its configuration from
``repro.configs`` and its optimizer from ``repro.optim``.
"""
