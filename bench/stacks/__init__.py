"""Serving stacks the benchmark can build, one module each.

A configuration file names its ``stack``; :func:`load` imports
``bench.stacks.<stack>`` and returns its ``Stack`` class.  A stack builds
the deployment from the seed, hands out one ``GraphClient`` per session
and graph, warms the shapes its traffic uses, and reads back counters and
final states.  It is the only part of the benchmark that imports the
system under test.
"""
from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"bench.stacks.{name}").Stack
