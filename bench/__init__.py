"""Chip benchmark of the versioned store, driven by ``BENCHMARK.json``.

One run is one cell (a configuration under a traffic mix) through the served
path, ``Repository.serve()`` -> ``DatasetService.commit``::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` -- the deployment's sizes, store and service
  settings, source, ``reduced`` and ``assumed``; ``bench/configs/<config>.py``
  beside it is its plain NumPy reference (every version's tree from the seed);
* ``bench/traffic/<mix>.json`` -- the parameters the one load generator
  (:mod:`bench.loadgen`) reads;
* ``bench/end_to_end/<metric>.py``, ``bench/layer_metrics/<metric>.py`` --
  one reader per metric.

The yardstick lives here too: the load generator, the profiler-trace
reduction and roofline arithmetic (:mod:`bench.trace`), the table of peaks
(``peaks.json``) and the comparison that decides ``correct``
(:mod:`bench.verify`).  Nothing here is imported by the program.
"""
