"""Plain float32 references, one module per model family, importing nothing
of the program. Each family module gives ``init_params`` (the benchmark's
own weights, made on the device from a seed in the program's parameter
layout and served dtype), ``forward`` (logits) and ``loss``; ``adamw`` is the
optimizer step. ``control=True`` computes every matrix product from float8
(e4m3) operands: the lower precision that the check must tell apart."""

import importlib


def family(name: str):
    """The reference module a configuration names (``"reference"`` key)."""
    return importlib.import_module(f"chipbench.reference.{name}")
