"""The chip benchmark: cells named in ``BENCHMARK.json`` at the repo root.

Everything a cell needs is found by name under this directory, so a new
deployment, traffic mix or metric is a set of new files:

- ``configs/<config>.json``   tables, rows, partition rows, engine, chips
- ``datasets/<dataset>.py``   ``build(rows, rng)`` → host tables
- ``mixes/<traffic>.json``    the ordered programs of a closed loop
- ``programs/<program>.py``   ``run`` (the engine), ``reference`` (plain
                              pandas) and ``check`` (the comparison)
- ``metrics/<metric>.py``     ``read(run)`` → a number, or ``None``
- ``limits/<workload>.json``  the limit of each number ``check`` compares

``run_cell.py`` runs one cell on the chip; ``control.py`` runs a cell's
lower-precision control.  The modules beside this file are the shared
yardstick: the run itself (``cell``), the comparison (``check``), compile
accounting (``compile_clock``), trace reduction (``trace``), the table of
device peaks (``peaks``) and the datasets' fast draws (``draw``).
"""
