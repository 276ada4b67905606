"""The chip benchmark's own code: everything that turns a cell named in
``BENCHMARK.json`` into one run's result line.  Nothing here is imported by
the program under test; the program is driven only through its entry
points (see ``bench/drivers``)."""
