"""The program's entry points as the benchmark drives them, one module per
``entry`` a cell file can name. Each builds its run in ``setup``, runs the
measured window in ``window`` and compares what the window produced with
the reference in ``check``."""

import importlib


def entry(name: str):
    return importlib.import_module(f"chipbench.entries.{name}")
