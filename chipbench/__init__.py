"""Chip benchmark of the training path: one cell, one run, one result line.

See ``chipbench/run.py`` for the command and ``BENCHMARK.json`` for the cells.
"""
