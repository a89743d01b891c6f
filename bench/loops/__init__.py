"""Closed loops, one per entry point of the program.  A traffic file
names its loop under ``"entry"``; the loop reads every other parameter
from that file and from the configuration.

A loop has ``setup()`` (inputs and one warm call), ``call(i)`` (the
``i``-th call of the window, its draws derived from the run's seed and
``i``), ``check(control=False)`` (the numbers compared with the
reference, over a sample of the window's calls drawn from the seed; with
``control`` the reference in the next lower precision stands in the
program's place) and ``span``, the name of the host span around a call.
"""
from __future__ import annotations

import importlib


def load(entry: str):
    """The loop class of a traffic file's ``entry``."""
    return importlib.import_module(f"bench.loops.{entry}").Loop
