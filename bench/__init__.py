"""The chip benchmark of Fed-RAC.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the repository names the cells, their
configurations and traffic mixes, and the metrics.  Everything that belongs
to one of them sits in a file of its own, found by its name, so a later
change adds files and entries and edits none:

* a configuration: ``bench/configs/<config>.json`` (the model, the data's
  shape and size, the participants, the federation settings, with
  ``reduced`` and ``assumed``), and in ``BENCHMARK.json`` an entry of
  ``configs`` whose ``file`` points there;
* a model family, named by a configuration's ``family``:
  ``bench/families/<family>.py`` (``build_engine``: the program's engine),
  ``bench/references/<family>.py`` (``init_params`` and ``run``: the plain
  float32 reference, which imports nothing of the program) and
  ``bench/flops/<family>.py`` (operation and byte counts);
* a traffic mix: ``bench/traffic/<mix>.json``, the settings that differ
  from ``harness.MIX_DEFAULTS``: overrides of the configuration's
  ``federation``, ``rounds_per_dispatch``, ``eval_every``;
* a cell: an entry of ``workloads`` naming a configuration and a mix, and
  ``bench/limits/<cell>.json``, the limit of each number that ``correct``
  compares (``bench/check.py``), set from readings that
  ``bench/calibrate.py`` takes on the chip;
* a per-layer metric: an entry of ``per_layer`` and
  ``bench/metrics/<metric>.py`` with ``read(win) -> float | None``; ``win``
  holds the traced window's length, its device busy time and per-operation
  times (``bench/tracefile.py``), the rounds, client steps and required
  operations completed in it, the program's spans and counters, and the
  chip's peaks (``bench/peaks.json``).  A reader that finds nothing to read
  returns ``None`` and the metric is left out of the line.

``tests/bench`` holds the benchmark's CPU tests.
"""
