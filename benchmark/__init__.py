"""The benchmark of mxnet-tpu: the yardstick, kept apart from the program.

``BENCHMARK.json`` at the root of the repo names the cells; ``run.py`` is
the one command.  Everything that belongs to one configuration, one
traffic mix or one metric sits in a file of its own that ``run.py`` finds
by name (see PERF.md, "Layers" and "Cells").
"""
