"""Back-compat shim — the serving stack is now a layered package.

The monolithic ``serve/engine.py`` split into:

    serve/lm.py         LM ``Engine`` + ``make_serve_fns``/``cache_pspecs``
    serve/registry.py   graph weights, memory accounting/LRU, dirty kinds
    serve/snapshot.py   double-buffered dist+succ snapshot store
    serve/scheduler.py  micro-batching query scheduler (max-batch/max-wait)
    serve/routing.py    public ``RoutingEngine`` (thin composition)

Import from those modules directly; this shim keeps the old
``from repro.serve.engine import RoutingEngine, Engine`` spelling working.
"""
from repro.serve.lm import (  # noqa: F401
    Engine,
    _params_bytes,
    cache_pspecs,
    make_serve_fns,
)
from repro.serve.routing import RouteReply, RoutingEngine  # noqa: F401

__all__ = [
    "Engine",
    "cache_pspecs",
    "make_serve_fns",
    "RouteReply",
    "RoutingEngine",
]
