"""Library entries the benchmark times, one module per entry.

A configuration names its entry (``"entry": "posv"``); the harness
imports ``benchmark.ops.<entry>`` and calls its ``build(cfg, devices)``,
which returns a :class:`Program`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass
class Program:
    """One library entry, ready to be compiled and called.

    ``fn(a, b) -> x`` is traced inside ``scope()``; ``a`` and ``b`` are
    placed with ``a_sharding`` and ``b_sharding``; ``flops`` is the
    LAWN-41 count of one call; ``check(compiled, platform)`` raises if
    the compiled program is not the path the cell is meant to time."""

    fn: Callable
    a_sharding: object
    b_sharding: object
    scope: Callable
    flops: float
    check: Callable = lambda compiled, platform: None
