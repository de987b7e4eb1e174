"""Entry ``solver_service``: ``dplasma_tpu.serving.SolverService`` as
its users run it. Requests of host NumPy operands are queued by
``submit``; the service groups them by executable key (op, shape
bucket, NRHS bucket), pads each group into its bucket, copies it to the
device and dispatches it as one batched program from its own timer
threads (``serving.max_wait_ms``) or at ``serving.max_batch``, then
checks every answer with its backward-error gate. The configuration's
``serving`` settings are the service's MCA settings, held for the life
of the server.

``warm`` reaches every program the traffic can: each op, n bucket and
NRHS bucket of the requests' shapes, at every batch bucket up to
``max_batch``. It warms through ``submit`` and ``flush``, the public
path, with identity problems at the bucket's own shape, so the service
compiles and caches each program as it would for traffic; a batch the
timer dispatched early is sent again until every key is in the cache.

Each program is the service's own, compiled under a module name of its
own (``jit_posv_n4096_r4_b1``), so that a trace's module runs say which
program ran (``benchmark/reduce.py`` ``name_by_program``)."""
from __future__ import annotations

import numpy as np

from benchmark.ops import Service


def _batch_buckets(max_batch: int):
    out, b = [], 1
    while True:
        out.append(b)
        if b >= max_batch:
            return out
        b *= 2


class _Server:
    def __init__(self, cfg: dict, wrap):
        from dplasma_tpu.serving import cache as cache_mod
        from dplasma_tpu.utils import config as mca
        s = cfg["serving"]
        self._frame = mca.push_overrides(
            {"serving.bucket": s["bucket"]}, label="benchmark")
        svc_cls = _service_class(wrap)
        self.svc = svc_cls(
            nb=int(cfg["nb"]), max_batch=int(s["max_batch"]),
            max_wait_ms=float(s["max_wait_ms"]),
            cache=cache_mod.ExecutableCache(
                capacity=int(s["cache_capacity"])))
        self._cache_mod = cache_mod
        self._dtype = np.dtype(cfg["dtype"])
        self._names: dict = {}       # CacheKey -> program name

    def _key(self, op: str, n: int, nrhs: int, batch: int):
        return self._cache_mod.make_key(op, n, self._dtype, batch, nrhs)

    @staticmethod
    def name(op: str, n: int, nrhs: int, batch: int) -> str:
        return f"{op}_n{n}_r{nrhs}_b{batch}"

    def warm(self, shapes) -> dict:
        groups = sorted({self._key(op, n, nrhs, 1)._replace(batch=0)
                         for op, n, nrhs in shapes})
        for g in groups:
            a = np.eye(g.n, dtype=self._dtype)
            b = np.zeros((g.n, g.nrhs), self._dtype)
            for batch in _batch_buckets(self.svc.max_batch):
                key = g._replace(batch=batch)
                self._names[key] = self.name(g.op, g.n, g.nrhs, batch)
                for _ in range(3):
                    futs = [self.svc.submit(g.op, a, b)
                            for _ in range(batch)]
                    self.svc.flush()
                    for f in futs:
                        f.result()
                    if key in self.svc.cache:
                        break
                else:
                    raise RuntimeError(f"could not warm {key}")
        self.svc.reset_stats()
        return {self._names[e.key]: e.fn for e in self.svc.cache.entries()}

    def submit(self, op: str, a, b):
        return self.svc.submit(op, a, b)

    @staticmethod
    def outcome(fut):
        if not fut.done():
            return None
        try:
            x = fut.result()
        except Exception as exc:   # the request's own failure, counted
            return exc, None, {}
        return x, fut.meta["latency_s"], fut.meta

    def dispatched(self, op: str, meta: dict):
        """The program that answered a request, its bucket shape and the
        number of requests its dispatch carried."""
        n, nrhs, batch = meta["bucket"]
        return self.name(op, n, nrhs, batch), n, nrhs, batch, meta["batch"]

    def spans(self):
        return [(s["name"], s.get("request"), s["t0_ns"], s["t1_ns"])
                for s in self.svc.telemetry.tracer.spans()]

    def close(self) -> None:
        from dplasma_tpu.utils import config as mca
        try:
            self.svc.close()
        finally:
            mca.pop_overrides(self._frame)


def _service_class(wrap):
    """``SolverService`` whose program for each key is compiled under
    the name ``_Server.name`` gives it. With ``wrap``, the program solves
    with ``wrap(fn)`` in place of the batched solves (the control and
    the tests' faults); else it is the service's own."""
    from dplasma_tpu.serving import SolverService, batched

    class Named(SolverService):
        def _builder(self, key, kwargs, nb=None):
            if wrap is None:
                build = super()._builder(key, kwargs, nb)
            else:
                nb_ = nb or self.nb
                solve = wrap(lambda op, a, b:
                             batched.solve_batched(op, a, b, nb_)[0])

                def build():
                    def body(a, b):
                        x = solve(key.op, a, b)
                        return x, batched.backward_errors(a, b, x)
                    return body

            def named():
                body = build()

                def program(a, b):
                    return body(a, b)
                program.__name__ = program.__qualname__ = _Server.name(
                    key.op, key.n, key.nrhs, key.batch)
                return program
            return named
    return Named


def build(cfg: dict, devices) -> Service:
    if len(devices) != 1:
        raise ValueError("the service runs on one device")
    return Service(start=lambda wrap=None: _Server(cfg, wrap))
