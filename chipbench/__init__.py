"""The chip benchmark: one command that runs one cell of ``BENCHMARK.json`` on
a TPU and prints its end-to-end or per-layer metrics, the device it ran on,
and whether what the timed path produced matches a plain float32 reference.

Everything a cell needs is data found by name: ``cells/<cell>.json`` (the
entry point and its settings), ``configs/<config>.json`` (the model's sizes
and the family of its reference), ``traffic/<traffic>.json`` (the mix that
:mod:`chipbench.traffic` generates), ``metrics/<metric>.py`` (one reader per
per-layer metric) and ``peaks.json`` (the chip's peaks by ``device_kind``).
"""
