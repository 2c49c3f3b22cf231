"""dmlcloud_tpu.serve — continuous-batching inference for heavy traffic.

The training stack's inference half (``models/generate.py``) runs one
static batch per call; this package turns it into a serving engine:

- :class:`KVBlockPool` (kv_pool.py): paged KV cache — fixed device pages,
  per-sequence block tables, host free list. Memory scales with live
  tokens; freed blocks recycle immediately.
- :class:`Scheduler` / :class:`Request` (scheduler.py): FIFO
  continuous-batching admission with chunked prefill — no drain barrier,
  no starvation.
- :class:`ServeEngine` (engine.py): the loop — bucketed decode shapes
  (0 mid-run recompiles, TraceGuard-enforced), greedy output
  token-identical to serial ``generate()``; per-REQUEST sampling params
  (mixed greedy/sampled tenants in one batch) and TWO speculative modes:
  draft-model decoding (``spec_k`` proposals per round against a second
  page pool, one k+1-position verify pass, partial-accept rewind by fill
  counters) and Medusa decoding (``medusa_k`` proposals from extra decode
  heads on the frozen base model — same verify and rewind, but the draft
  model, its prefill mirror and the whole second page pool are gone;
  ``models.speculative.init_medusa_heads`` shapes the heads).
- :class:`PrefixCache` (prefix_cache.py): radix-tree prefix sharing over
  content-addressed, refcounted pool blocks — a warm template's prefill
  shrinks to its unique suffix; copy-on-write forks protect shared pages;
  eviction is leaf-first LRU over refcount (``prefix_cache=True``).
- :class:`AdapterSet` (adapters.py): multi-tenant LoRA serving, one base
  model + per-request adapter deltas inside the decode step.
- :class:`ServeLedger` (ledger.py): TTFT / per-token / queue-depth
  latency accounting plus drafted/accepted counters and accept rates,
  journal span kinds ``queue_wait`` / ``prefill`` / ``decode_batch`` /
  ``draft`` / ``verify`` (``fault`` / ``drain`` on the failure paths);
  bounded retention (``max_records``) keeps the aggregates exact while
  per-request detail evicts FIFO.
- **Overload control & failure semantics** (scheduler.py + engine.py):
  per-request ``deadline_s`` / ``priority`` / ``tenant``, ``cancel(rid)``
  at any phase, one terminal status per request (``ok | cancelled |
  deadline_exceeded | shed | error``), bounded admission queue with load
  shedding (``max_waiting`` + ``shed_policy``), per-tenant deficit-
  round-robin fairness (``fairness="tenant"``), per-request fault
  isolation and graceful drain (``drain()`` — admission stops, in-flight
  work finishes inside ``drain_budget_s``, the ``requeue.json`` verdict
  is written).
- :class:`ChaosMonkey` (chaos.py): seeded deterministic fault injection
  — step exceptions, pool-exhaustion squats, slow-clock stalls, random
  cancels, and (attached to a router) replica kills and stalls — the
  drill that proves the above under fire.
- :class:`Router` (router.py): the multi-replica front door — N engine
  replicas behind one submit/step surface: heartbeat health detection,
  at-most-once failover via idempotency tokens (``DuplicateRequest`` is
  the engine-side guard), per-tenant deficit-round-robin placement with
  stable prefix-affinity hints (``prefix_keys``), per-replica circuit
  breakers, and router-coordinated graceful drain of one replica.
- **Observability plane** (doc/observability.md): request-scoped tracing
  — the router mints one trace id per request and every span it touches
  (``route``/``queue_wait``/``admission``/``prefix_lookup``/``prefill``/
  ``cow_fork``/decode batches/``failover``) links into a single causal
  trace across replicas and retries; a typed metrics registry
  (``ServeEngine(metrics=True)``, ``engine.metrics_text()`` /
  ``Router.metrics_text()``, optional :class:`MetricsServer` HTTP
  endpoint, ``python -m dmlcloud_tpu top``); and declarative
  :class:`SLO` objectives with multi-window burn-rate alerting
  (:class:`SLOMonitor`, ``slos=`` — alerts journal as ``slo_alert``
  spans and surface in the ledger summary, ``diag --run`` and the drain
  verdict).

Quick start::

    from dmlcloud_tpu.serve import ServeEngine

    engine = ServeEngine(model, params, num_blocks=256, block_size=16,
                         max_slots=8)
    rid = engine.submit(prompt_tokens, max_new_tokens=64)
    engine.run()
    tokens = engine.output(rid)

See doc/serving.md for the architecture, the memory math and the tests that
hold each contract.
"""

from .adapters import AdapterSet
from .chaos import ChaosError, ChaosMonkey
from .engine import DuplicateRequest, ServeEngine
from .kv_pool import KVBlockPool, PoolExhausted
from .ledger import ServeLedger
from .metrics_http import MetricsServer
from .prefix_cache import PrefixCache, PrefixMatch, prefix_keys
from .router import Router
from .scheduler import Request, Scheduler, TERMINAL_STATUSES
from .slo import SLO, SLOMonitor

__all__ = [
    "AdapterSet",
    "ChaosError",
    "ChaosMonkey",
    "DuplicateRequest",
    "KVBlockPool",
    "MetricsServer",
    "PoolExhausted",
    "PrefixCache",
    "PrefixMatch",
    "Request",
    "Router",
    "SLO",
    "SLOMonitor",
    "Scheduler",
    "ServeEngine",
    "ServeLedger",
    "TERMINAL_STATUSES",
    "prefix_keys",
]
