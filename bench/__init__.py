"""The chip benchmark: one data-driven harness (``bench/run.py``) over the
cells that ``BENCHMARK.json`` names.  Everything a cell needs is found by
name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``, ``limits/<workload>.json`` and the reference that
the configuration names under ``reference/``."""
