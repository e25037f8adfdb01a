"""Kinds of call a traffic mix sends, one module each, found by the name
in the traffic file's ``kind`` (``bench/kinds/<kind>.py``).

A kind module has five functions:

* ``call(traffic, cfg, seed, i) -> dict`` -- the ``i``-th call of the mix
  as plain data (numpy and built-ins only), with ``designs``, the work it
  asks for, counted from the traffic file and never from what the program
  reports. The same arguments always give the same call. A generator
  that needs the graph or the package takes them from the configuration's
  reference (``bench.harness.check.reference_of``).
* ``warm_calls(traffic, cfg, seed) -> list[dict]`` -- calls that reach
  every executable the window will run, on inputs the window never sends.
* ``prepare(system, call)`` -- the program's arguments for ``call``,
  built with ``bench.harness.sut.System``; outside the timed call.
* ``run(system, call, args, cache=True)`` -- the timed call itself.
* ``check(checks, ref, traffic, window, seed)`` -- after the window, the
  answers in ``window.answers`` compared with ``ref``
  (``bench.harness.check.Reference``), each number into ``checks``
  under a name that the traffic file's ``limits`` gives a limit.

Adding a kind is adding its module and a traffic file that names it.
"""
