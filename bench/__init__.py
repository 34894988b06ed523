"""Chip benchmark of the versioned store, driven by ``BENCHMARK.json``.

One run is one cell (a configuration under a traffic mix) through the served
path, ``Repository.serve()`` -> ``DatasetService.commit``::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it.  A new
configuration and its cell are these new files, and no edit to a file that is
already here:

* ``bench/configs/<config>.json`` -- the deployment's sizes, store and service
  settings, source, ``reduced`` and ``assumed``;
* ``bench/configs/<config>.py`` -- its plain NumPy reference (below);
* ``bench/configs/<config>.tiny.json`` -- the keys that replace the
  configuration's in the CPU rehearsal (:mod:`bench.conftest`), so that its
  cells run at a tiny size in the per-cell suites;
* ``bench/test_config_<config>.py`` -- what a save of this configuration
  changes (which blocks, which leaves grow), checked on the reference;
* ``bench/traffic/<mix>.json`` -- the parameters the one load generator
  (:mod:`bench.loadgen`) reads, where the cell's mix is new;
* ``bench/end_to_end/<metric>.py``, ``bench/layer_metrics/<metric>.py`` --
  one reader per metric, where a metric is new;
* in ``BENCHMARK.json``: the ``configs`` and ``workloads`` entries, any new
  metric's entry, and the cell's name appended to the ``workloads`` list of
  each metric it reports.

The per-cell suites (``test_bench_cells.py``, ``test_bench_faults.py``) then
run the cell at its tiny size, hold it to its control and to the planted
faults, and need no edit.

A reference module is independent of the store and defines, each a pure
function of its arguments:

* ``base_tree(cfg, seed)`` -- version 0's tree, ``{leaf name: np.ndarray}``,
  from the seed; every seed gives the same leaves, shapes and dtypes;
* ``edit(cfg, seed, index)`` -- save ``index``'s change, from the seed and
  the index alone;
* ``apply(cfg, tree, change)`` -- the parent ``tree`` with ``change``
  applied, as a new tree (the parent is not modified);
* ``control(cfg, tree, parent)`` -- the answer one step below what the
  configuration states (a lower precision, or one broken guarantee), which
  the comparison must find wrong; ``parent`` may be ``None``.

The yardstick lives here too: the load generator, the profiler-trace
reduction and roofline arithmetic (:mod:`bench.trace`), the table of peaks
(``peaks.json``) and the comparison that decides ``correct``
(:mod:`bench.verify`).  Nothing here is imported by the program.
"""
