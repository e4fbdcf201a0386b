"""chipbench: the on-chip benchmark of this serving stack.

Everything that decides a number lives here, under a path a later PR
may add to and not edit: traffic generation, window accounting, the
trace reduction, the table of peaks, the roofline functions, the plain
reference and the comparison that decides ``correct``. From the program
it takes only the system under test (router and engine, started through
their normal entry points) and what they expose: ``/debug/perf``,
``/debug/traces``, ``/metrics``, ``/load`` and kernel names in the
device trace. See README.md for how to add a cell without editing a
file that is there.
"""
