"""Library entries the benchmark times, one module per entry.

A configuration names its entry (``"entry": "posv"``); the harness
imports ``benchmark.ops.<entry>`` and calls its ``build(cfg, devices)``,
which returns a :class:`Program` (one compiled call the harness drives)
or a :class:`Service` (a server that takes requests and compiles its
own programs).
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


@dataclasses.dataclass
class Service:
    """A request-serving entry.

    ``start(wrap)`` starts the server and returns it; ``wrap``, when not
    None, replaces the server's batched solve ``fn(op, a, b) -> x``
    (stacked ``(batch, n, n)`` and ``(batch, n, nrhs)``) by ``wrap(fn)``,
    for the control and the faults of the tests. The server has:

    * ``warm(shapes)``: compiles and runs once every program that
      requests of these ``(op, n, nrhs)`` shapes can reach, and returns
      ``{name: compiled}`` of them;
    * ``submit(op, a, b)``: queues one request of host NumPy operands
      and returns its handle at once, without waiting;
    * ``outcome(handle)``: ``None`` while pending, else ``(x, latency_s,
      meta)``, ``x`` an exception where the request failed; latency
      runs from the submit to the answer;
    * ``dispatched(op, meta)``: for an answered request, ``(program
      name, n, nrhs, batch, requests)``: the program (named as ``warm``
      names it) whose dispatch answered it, that program's bucket
      shape and batch, and how many requests the dispatch carried;
    * ``spans()``: the server's own spans as
      ``(name, request id or None, t0_ns, t1_ns)`` on ``time.time_ns``;
    * ``close()``."""

    start: Callable
