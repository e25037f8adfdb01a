"""Plain references, one module per kind of configuration, each found by
name: a configuration file's ``"reference": "<name>"`` names the module
``bench/reference/<name>.py``; without the key it is ``mcm``
(``bench.harness.check.reference_of``). A reference module provides:

* ``graph_ops(workload) -> list`` -- the task graph that the
  configuration's ``workload`` describes, as ops in program order, each
  with at least ``name``, ``M``, ``K``, ``N``;
* ``package(cfg, **variant)`` -- the package the configuration states,
  with the fields a traffic file varies changed; an object with at least
  ``X``, ``Y``, ``R``, ``C``;
* ``Reference(ops, pk, options)`` -- the scorer of partitions of ``ops``
  on ``pk`` under the configuration's ``options``, with ``.pk``,
  ``check_partition(Px, Py, collectors)`` (why a genome is no partition,
  or None), ``redist_of(mask=None)`` (the redistribution genes a point
  means) and ``evaluate(Px, Py, collectors, redist) -> dict`` (every field
  of ``bench.harness.check.EVAL_KEYS``), as ``mcm`` has them.

A reference imports nothing of the system under test (``repro``) and takes
nothing it made. A new one may import ``bench.reference.mcm`` and reuse or
subclass its parts. Adding an architecture is adding its module, its
configuration file naming it, and the program's own graph.
"""
