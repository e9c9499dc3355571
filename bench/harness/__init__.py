"""The benchmark's own code: traffic, the trace reduction, counts of
operations and bytes, and the comparison that decides ``correct``.  Only
``runner.py`` and the configurations' ``.serve.py`` adapters import the
program, to drive the system under test; the references import nothing of
it."""
