"""The served path as users drive it: one ``AlchemistServer`` on a
localhost port around an ``AlchemistEngine`` with its default options,
and client ``AlchemistContext(address=...)`` sessions over real TCP.

Also the closed loop that drives it, and the benchmark's own host spans
(``bench.*``), which go into the profiler's trace when one is taken.
"""
from __future__ import annotations

import dataclasses
import sys
import threading
import time

import jax

from repro.core import AlchemistContext, AlchemistEngine
from repro.core.engine import make_engine_mesh
from repro.core.libraries import elemental, skylark
from repro.core.server import AlchemistServer

class Spans:
    """Host spans of the benchmark: kept in memory, and written into the
    profiler's trace while one is taken."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []
        self._lock = threading.Lock()

    def __call__(self, name: str):
        return _Span(self, "bench." + name)


class _Span:
    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name

    def __enter__(self):
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.annotation.__exit__(*exc)
        with self.spans._lock:
            self.spans.items.append((self.name, self.t0, t1))
        return False


class Served:
    """The server, its engine, and the client sessions opened on it."""

    def __init__(self, chips: int):
        self.engine = AlchemistEngine(make_engine_mesh(chips))
        self.server = AlchemistServer(engine=self.engine, host="127.0.0.1",
                                      port=0).start()
        self.clients: list[AlchemistContext] = []
        with AlchemistContext(address=self.server.address,
                              client_name="loader") as ac:
            ac.register_library("elemental", elemental)
            ac.register_library("skylark", skylark)

    def client(self, name: str) -> AlchemistContext:
        ac = AlchemistContext(address=self.server.address, client_name=name)
        self.clients.append(ac)
        return ac

    def stop(self) -> None:
        for ac in self.clients:
            ac.stop()
        self.server.stop(shutdown_engine=True)


@dataclasses.dataclass
class Call:
    """One request of the window, as the client saw it."""
    request: dict
    start: float
    end: float | None = None
    answer: dict | None = None
    error: str | None = None


def closed_loop(requests, do, seconds: float, spans: Spans) -> list[Call]:
    """Send each request when the last is answered, until ``seconds`` have
    passed since the first was sent."""
    t_end = time.perf_counter() + seconds
    calls: list[Call] = []
    for req in requests:
        if time.perf_counter() >= t_end:
            break
        call = Call(req, time.perf_counter())
        try:
            with spans("call"):
                call.answer = do(req)
        except Exception as e:           # a failed call counts, not aborts
            call.error = f"{type(e).__name__}: {e}"
        call.end = time.perf_counter()
        calls.append(call)
    if len(calls) == len(requests) and time.perf_counter() < t_end:
        print(f"bench: the window closed early: all {len(requests)} "
              "prepared requests were sent", file=sys.stderr)
    return calls
