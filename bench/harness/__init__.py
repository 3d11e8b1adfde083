"""Shared code of the on-chip benchmark.

`run.py` finds a cell's files by the names in `BENCHMARK.json`
(`cells.py`), sets it up with the serving or the training driver,
measures one window, decides `correct` against the plain reference of
the configuration's architecture (`arch/<name>.py`, `check.py`) and
prints one result line. Nothing here names a cell, a mix, a metric or an
architecture: those live in their own files under `configs/`, `traffic/`,
`limits/`, `metrics/` and `arch/`.
"""
