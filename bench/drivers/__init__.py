"""Drivers, one module each, found by the name in a traffic file's
``driver`` (``bench/drivers/<driver>.py``). A driver has one function,

    drive(system, kind, traffic, cfg, seed, seconds, clock, on_open,
          on_close) -> bench.harness.drive.Window

which warms up, calls ``on_open()`` as the measured window opens and
``on_close()`` as it closes, and returns what the window measured, with
every answer in ``Window.answers`` for the kind's check. Adding a driver
(an open loop of requests, say) is adding its module.
"""
