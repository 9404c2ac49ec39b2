"""Chip benchmark of MEMHD: one cell (configuration x traffic) per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once and prints one
JSON result line. Everything a cell needs is found by name: the
configuration in ``bench/configs/<config>.json``, the builder of its
system under test in ``bench/builders/<system>.py`` (the configuration's
``system`` key), the traffic mix in ``bench/traffic/<traffic>.json`` and
its mode in ``bench/modes/<mode>.py`` (the traffic's ``mode`` key), the
correctness limits in ``bench/limits/<workload>.json`` and each
per-layer metric's reader in ``bench/metrics/<metric>.py``.

So a new configuration comes as new files only:

* ``bench/configs/<config>.json``, its sizes;
* ``bench/builders/<system>.py``, whose ``build(cfg, seed)`` makes its
  weights and rows from the seed and gives its reference's answers;
* that reference, where no existing one fits, as
  ``bench/reference_<name>.py``, importing nothing of the program;
* ``bench/traffic/<traffic>.json``, where its mix is new;
* ``bench/limits/<workload>.json`` for each of its cells;
* ``bench/metrics/<metric>.py`` for each new per-layer metric;

and additions to ``BENCHMARK.json``: the configuration, its cells, their
new per-layer metrics, and each cell's name in the ``workloads`` list of
the end-to-end metric it reports.
"""
