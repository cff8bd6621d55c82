"""FrogWild! reproduction package — served through one facade.

The public surface is the **service API** (``repro/service.py``)::

    from repro import FrogWildService, RuntimeConfig

    svc = FrogWildService.open(graph_or_path, RuntimeConfig())
    res = svc.pagerank(epsilon=0.3, delta=0.1)      # batch (auto dispatch)
    h = svc.topk(k=10, epsilon=0.3)                 # async QueryHandle
    while not h.poll():
        print(h.partial().epsilon_bound)            # anytime: tightens
    print(h.result().vertices)

``FrogWildService.open`` owns graph ingestion, shard-runtime acquisition,
and the walk-index lifecycle (build / load / reuse through ``checkpoint/``);
``topk`` / ``ppr`` return :class:`~repro.service.QueryHandle` futures whose
``partial()`` snapshots carry a monotonically tightening Theorem-1
``epsilon_bound`` and which complete early once the requested (ε, δ) target
is met. Configuration is the layered :class:`~repro.config.RuntimeConfig`
(kernel + runtime + serving sub-configs, one definition per flag).

Above the facade sits the **serving gateway** (``repro/gateway/``): a
replica pool over one shared graph/walk-index, an (ε, δ)-aware result
cache with in-flight dedup (dominance contract: a cached certificate
(ε′, δ′) serves a request (ε, δ) iff ε′ ≤ ε and δ′ ≤ δ), and a metrics /
health layer with a stdlib HTTP front-end — ``Gateway.open(graph,
replicas=2)``.

The historical entry points (``frogwild_run``, ``distributed_frogwild``,
``build_walk_index{,_sharded}``, ``QueryScheduler.submit/run``) remain as
deprecation shims that delegate through the service and return
byte-identical results.
"""
from repro.config import (KernelConfig, RuntimeConfig, ServingConfig,
                          ShardConfig)
from repro.gateway import Gateway
from repro.service import FrogWildService, QueryHandle

__all__ = [
    "FrogWildService",
    "Gateway",
    "QueryHandle",
    "RuntimeConfig",
    "KernelConfig",
    "ShardConfig",
    "ServingConfig",
]
