"""The continuous-batching serving engine over the paged KV pool.

One :class:`ServeEngine` owns the four pieces the module docstrings around
it describe — the device page pool (``kv_pool``), the FIFO scheduler
(``scheduler``), the per-request latency ledger (``ledger``) and the
jitted paged steps — and runs the serving loop:

    admit waiting requests -> one prefill chunk -> one decode batch

per :meth:`step`. The decode batch advances EVERY running stream
regardless of how much prefill is pending, so a long prompt never
stalls running generations; a stream that emits EOS frees its slot and
blocks before the next step, and the next waiting request takes them —
continuous batching, no drain barrier.

**Three decode modes share that loop:**

- *Plain* (``spec_k == medusa_k == 0``): one jitted ``_paged_step``
  advances every row one token per step — the PR-8 engine, unchanged
  semantics.
- *Speculative* (``spec_k >= 1``): a draft model (or the target itself —
  shared-model self-draft, ``models/speculative.py``'s smoke config)
  proposes ``k`` tokens per round against its OWN page pool, and one
  verifier pass scores all ``k+1`` positions per row through the same
  ``ops/paged_attention.py`` scatter/gather (multi-token writes through
  the block tables; sentinel rows still drop). The accept rule is
  :func:`models.speculative.verify_proposals` with each row's own
  sampling params; a partial accept "rewinds" by advancing the host-side
  fill counters only to the accepted position — block ownership never
  moves, and the next round's contiguous writes overwrite the stale
  speculative tail before the causal mask can expose it (the same
  overwrite invariant ``speculative_generate`` proves). Per accepted
  token the target pays ``~1/(accepted+1)`` of a weight-streaming pass —
  the per-token cost of the weight-bandwidth-bound decode loop becomes a
  per-round cost.
- *Medusa* (``medusa_k >= 1``): the separate draft model, its prefill
  mirror and the entire second page pool are GONE from the speculative
  path. ``k - 1`` lightweight decode heads
  (:func:`models.speculative.init_medusa_heads` — one residual block
  each, riding the FROZEN base model) read the final hidden state out of
  the round's ONE verify forward (``decode_step(...,
  return_hidden=True)``) and emit the NEXT round's proposals on the way
  out, so a round is a single ``k``-position target pass committing up
  to ``k`` tokens — the proposals ride the round's packed token fetch as
  ``k - 1`` host ints, never a second forward. Verification is the SAME
  ``verify_proposals`` + fill-counter rewind as spec mode (proposals are
  the heads' argmax picks, i.e. one-hot draft rows — rejection sampling
  stays exact for sampled rows), fused into the ONE ``_medusa_step``
  signature per (batch x table) bucket — the signature budget SHRINKS vs
  spec mode (no draft prefill, no second per-round step) and
  ``leaked_blocks`` has no draft pool to count. Because there is only
  one model, the heads propose from the ADAPTED hidden state under
  per-row LoRA — the proposer sees the tenant delta spec mode's
  base-model draft never did.

**Prefix sharing** (``prefix_cache=True``, serve/prefix_cache.py): pool
blocks become content-addressed and refcounted, indexed by a radix tree
over token prefixes. Admission maps a prompt's longest cached full-block
prefix READ-ONLY into the new request's table and starts chunked prefill
at the divergence point — a warm template's prefill shrinks to its unique
suffix (near-zero TTFT). Every write path runs a copy-on-write guard
first (``_cow_guard``: fork any refcount>1 block the scatter would touch
— one traced ``_copy_block`` signature for every fork ever; lint DML211
enforces the ordering), and the pool evicts leaf-first by LRU over
refcount when the free list runs dry. Greedy output stays token-identical
to the uncached engine (tests/test_serve_prefix.py::TestPrefixEngine).

**Per-request sampling.** ``temperature``/``top_k``/``top_p``/``eos_id``
ride each :class:`Request` and enter the compiled steps as per-row traced
arrays (``models.generate.sample_logits_batched``), so one engine serves
mixed greedy/sampled tenants in a single batch; greedy rows stay
bit-identical to serial ``generate()``.

**Failure semantics.** Every request ends in exactly one terminal status
(``ok | cancelled | deadline_exceeded | shed | error`` — see
:data:`~dmlcloud_tpu.serve.scheduler.TERMINAL_STATUSES`), through ONE
exit path (``Scheduler.terminate``) that releases both pools, the COW
spare and any prefix-cache locks at ANY phase — queued, mid-chunked-
prefill, mid-decode, mid-spec-round. A step failure is isolated to the
request(s) it was advancing: the engine catches it, fails those rows
(status ``error``, blocks freed, a ``fault`` span in the journal) and
keeps serving everyone else — greedy survivors stay token-identical to
an un-injected run (``serve/chaos.py`` proves this deterministically).
A failed DRAFT step degrades that round to plain decode instead (the
draft is an optimization; losing one round costs accept-rate
bookkeeping nothing). Overload control bounds the admission queue
(``max_waiting`` + ``shed_policy``) and a per-tenant deficit-round-robin
mode (``fairness="tenant"``) keeps a hot tenant from starving cold ones.
Graceful drain (:meth:`ServeEngine.drain`, or automatically when the
installed ``PreemptionGuard`` trips mid-``step``) stops admission, sheds
the queue, lets in-flight work finish inside ``drain_budget_s`` (then
sheds it too) and writes the ``requeue.json`` verdict every elasticity
wrapper already reads (doc/elasticity.md).

**Zero mid-run recompiles, by construction.** Every device call's shape
signature is ``(batch_bucket, table_bucket)`` for decode (each of the
draft and verify steps in spec mode) and ``(1, prefill_chunk,
table_bucket)`` for prefill — times two prefill models in spec mode —
with both bucket sets fixed at engine construction (``compile/buckets.py``
machinery). Each jitted step is wrapped in a ``TraceGuard`` armed at
exactly its bucket product, so a signature leak is a raised
``RetraceError`` in tests rather than a silent compile stall under
production traffic.

**One host sync per device round.** The fetched array IS the output
(tokens), and in spec mode the per-row ``n_new``/``n_accept`` counters
ride THAT SAME fetch as two extra packed columns — no separate
``.item()``/``int()`` readback of accept counters anywhere in the loop
(lint rule DML210 exists because a per-round counter readback is one more
host sync a round).

The decode math itself is :func:`models.generate.decode_step` — the same
primitive ``generate``/``beam_search``/``speculative_generate`` run — with
``pages=(block_tables, fill)`` steering it through the pool
(``ops/paged_attention.py``). ``prepare_decode_params`` is applied once at
construction for both models: int8 weight-only trees serve with the
fused-dequant kernels and the off-TPU operand widen pre-paid (the PR-6
decode win), with no per-call preparation left in the loop.
"""

from __future__ import annotations

import collections
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..compile.buckets import bucket_for, resolve_buckets
from ..lint.traceguard import TraceGuard
from ..telemetry import journal
from .adapters import AdapterSet
from .kv_pool import KVBlockPool
from .ledger import ServeLedger
from .prefix_cache import PrefixCache
from .scheduler import Request, Scheduler, _Sequence

__all__ = ["DuplicateRequest", "ServeEngine"]


class DuplicateRequest(ValueError):
    """``submit`` rejected an idempotency token it has already accepted —
    the original admission stands. Carries the rid it mapped to, so a
    retrying caller can re-attach instead of double-admitting."""

    def __init__(self, token: str, rid: int):
        super().__init__(
            f"idempotency token {token!r} already admitted as request {rid}"
        )
        self.token = token
        self.rid = int(rid)


def _copy_block(pools, src, dst):
    """The copy-on-write fork's device half: copy page ``src`` to page
    ``dst`` across every layer's K/V leaves. ``src``/``dst`` are TRACED
    scalars, so every fork in the engine's lifetime replays ONE compiled
    signature (a Python-int ``.at[i].set`` would bake the ids in and
    compile per (src, dst) pair — a mid-run recompile per fork).
    ``pools`` is donated: the fork is a swap, never two live pools."""
    return jax.tree_util.tree_map(lambda x: x.at[dst].set(x[src]), pools)


def _paged_step(
    pools, params, tables, fill, tokens, last_idx, rng, adapters,
    temperature, top_k, top_p, *, model,
):
    """One traced engine step (prefill chunk or plain decode batch): write
    ``tokens``' K/V through the block tables, read each row's logits at
    ``last_idx`` and sample the next token with each ROW's params (traced
    ``[B]`` arrays — mixed greedy/sampled tenants share the trace, and a
    new temperature never recompiles). ``pools`` is donated — the engine
    swaps in the returned pages (DML205: never two live copies of the
    cache)."""
    from ..models.generate import decode_step, sample_logits_batched

    logits, pools = decode_step(
        model, params, tokens, pools, pages=(tables, fill), adapters=adapters
    )
    with jax.named_scope("head"):
        last = jnp.take_along_axis(logits, last_idx[:, None, None], axis=1)[:, 0]  # [B, V]
    tok = sample_logits_batched(last, rng, temperature, top_k, top_p)
    return tok, pools


def _spec_draft_step(
    pools, params, tables, fill, prev_tok, last_tok, rng,
    temperature, top_k, top_p, *, model, k,
):
    """The draft half of one speculative round: ``k`` proposals per row
    against the draft page pool, all shapes static. Pass 0 feeds the last
    TWO committed tokens at positions ``fill-1``/``fill`` — the leading
    rewrite closes the draft pool's one-slot gap after a fully-accepted
    round (``models/speculative.py``'s 2-token trick) and is an identical
    rewrite otherwise; passes ``1..k-1`` feed each proposal at
    ``fill + i``. Returns ``(proposals [B, k], dlogits [B, k, V],
    pools)`` where ``dlogits`` row ``i`` is the truncated, scaled
    distribution proposal ``i+1`` was sampled from — exactly what the
    verifier's rejection rule needs as ``p_d``. ``pools`` is donated."""
    from ..models.generate import _truncate_scaled, decode_step, sample_logits_batched

    def pick(row_logits, i):
        return sample_logits_batched(
            row_logits, jax.random.fold_in(rng, i), temperature, top_k, top_p
        )

    toks2 = jnp.stack([prev_tok, last_tok], axis=1)  # [B, 2]
    logits, pools = decode_step(model, params, toks2, pools, pages=(tables, fill - 1))
    nxt = pick(logits[:, -1], 0)
    props, drows = [nxt], [logits[:, -1]]
    for i in range(1, k):  # k-1 single-token passes (unrolled: k is static)
        logits, pools = decode_step(
            model, params, nxt[:, None], pools, pages=(tables, fill + i)
        )
        nxt = pick(logits[:, 0], i)
        props.append(nxt)
        drows.append(logits[:, 0])
    proposals = jnp.stack(props, axis=1)  # [B, k]
    dlogits = _truncate_scaled(
        jnp.stack(drows, axis=1).astype(jnp.float32), temperature, top_k, top_p
    )
    return proposals, dlogits, pools


def _spec_verify_step(
    pools, params, tables, fill, last_tok, proposals, dlogits, rng,
    temperature, top_k, top_p, eos_id, adapters, *, model, k,
):
    """The verify half: ONE target pass scores all ``k+1`` positions per
    row (``[y_last, d_1..d_k]`` written at ``fill..fill+k`` through the
    block tables), then :func:`models.speculative.verify_proposals` runs
    each row's own accept rule. Returns ``(packed [B, k+3], pools)`` —
    the ``k+1`` tokens to commit plus the ``n_new``/``n_accept`` counters
    as two extra columns, so ONE host fetch carries tokens AND counters
    (no separate counter readback per round — DML210). ``adapters``
    threads per-row LoRA deltas into the TARGET pass only (spec × LoRA:
    the base-model draft proposes without the tenant's delta — it only
    costs accept rate; the verifier scores with the adapter, so output
    stays token-identical to the tenant's own model). ``pools`` is
    donated."""
    from ..models.generate import decode_step
    from ..models.speculative import verify_proposals

    x = jnp.concatenate([last_tok[:, None], proposals], axis=1)  # [B, k+1]
    tlogits, pools = decode_step(
        model, params, x, pools, pages=(tables, fill), adapters=adapters
    )
    new_tokens, n_new, n_accept = verify_proposals(
        tlogits, dlogits, proposals, rng, temperature, top_k, top_p, eos_id
    )
    packed = jnp.concatenate(
        [new_tokens, n_new[:, None], n_accept[:, None]], axis=1
    )
    return packed, pools


def _medusa_step(
    pools, params, heads, tables, fill, last_tok, proposals, rng,
    temperature, top_k, top_p, eos_id, adapters, *, model, k,
):
    """One whole Medusa round as a SINGLE model forward: the round's
    proposals were produced by the PREVIOUS round's forward (the heads
    read its final hidden state), so this step only verifies them and
    emits the next round's proposals on the way out — no draft model, no
    second pool, no second prefill, no dedicated propose pass anywhere.

    Verify: the spec-mode shape shrunk by one — ``[y_last, q_1..q_{k-1}]``
    written at ``fill..fill+k-1`` through the block tables, then
    :func:`models.speculative.verify_proposals` with each row's own
    params. Proposals are the heads' ARGMAX picks, so each draft
    distribution is exactly one-hot at the proposed token — rejection
    sampling against a one-hot ``q`` preserves every sampled row's
    truncated target distribution exactly (accept w.p. ``p_t(q)``, else
    sample the renormalised residual), and greedy rows stay
    token-identical to plain decode at ANY accept rate.

    Propose (for the NEXT round): ``hidden[:, n_accept]`` is the state
    that produced this round's correction token, so head ``h``
    (``models.speculative.medusa_head_logits`` — one fused matmul pair,
    not k-1 extra forwards) predicts the ``(h+1)``-th token after it.
    Unlike spec mode the proposer sees the tenant's LoRA delta for free —
    the heads read the ADAPTED hidden state out of the verify forward.
    ``k == 1`` has no heads and degenerates to plain one-token decode
    through the medusa signature.

    Returns ``(packed [B, 2k+1] (k>1) / [B, 3] (k=1), pools)`` — committed
    tokens, the ``n_new``/``n_accept`` counters AND the next proposals in
    ONE fetch (DML210). ``pools`` is donated."""
    from ..models.generate import decode_step, sample_logits_batched
    from ..models.speculative import medusa_head_logits, verify_proposals

    x = (
        jnp.concatenate([last_tok[:, None], proposals], axis=1)
        if k > 1 else last_tok[:, None]
    )  # [B, k]
    (tlogits, hidden), pools = decode_step(
        model, params, x, pools, pages=(tables, fill),
        adapters=adapters, return_hidden=True,
    )
    tlogits = tlogits.astype(jnp.float32)
    if k == 1:
        tok = sample_logits_batched(tlogits[:, 0], rng, temperature, top_k, top_p)
        packed = jnp.stack(
            [tok, jnp.ones_like(tok), jnp.zeros_like(tok)], axis=1
        )
        return packed, pools
    vocab = tlogits.shape[-1]
    dlogits = jnp.where(
        jax.nn.one_hot(proposals, vocab, dtype=bool), 0.0, -1e9
    )  # one-hot at the argmax pick the proposal actually was
    new_tokens, n_new, n_accept = verify_proposals(
        tlogits, dlogits, proposals, rng, temperature, top_k, top_p, eos_id
    )
    h_acc = jnp.take_along_axis(hidden, n_accept[:, None, None], axis=1)[:, 0]
    nxt = jnp.argmax(medusa_head_logits(heads, h_acc), axis=-1).astype(jnp.int32)
    packed = jnp.concatenate(
        [new_tokens, n_new[:, None], n_accept[:, None], nxt], axis=1
    )
    return packed, pools


def _pow2_buckets(limit: int) -> tuple[int, ...]:
    """1, 2, 4, ... capped at (and always including) ``limit``."""
    out, b = [], 1
    while b < limit:
        out.append(b)
        b *= 2
    out.append(int(limit))
    return resolve_buckets(out)


class ServeEngine:
    """Continuous-batching inference over a DecoderLM (module docstring).

    Construction knobs:

    - ``num_blocks`` / ``block_size``: the pool geometry. The default pool
      covers ``max_slots`` worst-case sequences — safe but dense-sized;
      real deployments size it for the EXPECTED live tokens (the whole
      point of paging) and let admission control do the rest.
    - ``max_slots``: concurrent decode streams; ``batch_buckets`` /
      ``table_buckets`` default to powers of two capped at the maxima.
    - ``prefill_chunk``: prompt tokens processed per engine step.
    - sampling (``temperature``/``top_k``/``top_p``/``eos_id``): the
      ENGINE DEFAULTS (greedy, ``generate()`` semantics); each request
      may override any of them (``submit``), and the per-row values ride
      the compiled step as traced arrays.
    - ``spec_k``: speculative proposals per verification round; 0 (the
      default) is the plain one-token-per-step engine. ``draft_model`` /
      ``draft_params`` name the proposer (both None = shared-model
      self-draft: the target drafts for itself — the correctness smoke,
      accept rate exactly 1.0 under greedy); ``draft_num_blocks`` sizes
      the draft page pool (default: the target pool's count).
    - ``medusa_k`` / ``medusa_heads``: Medusa decoding — up to ``medusa_k``
      tokens per round from ``medusa_k - 1`` extra decode heads on the
      frozen base model, one ``k``-position forward per round (mutually
      exclusive with ``spec_k``; no draft model, no draft pool).
      ``medusa_heads`` is the
      :func:`models.speculative.init_medusa_heads`-shaped stack (usually
      distilled offline); None warm-starts every head from the base
      ``lm_head`` — correct but with self-agreement accept rates only.
      ``medusa_k=1`` has no heads and degenerates to plain one-token
      decode through the medusa signature — the correctness smoke.
      Output is token-identical to plain decode at ANY accept rate.
    - ``adapters``: an :class:`AdapterSet` for multi-tenant LoRA serving;
      requests pick a tenant by name. Composes with ``spec_k``: the
      base-model draft proposes WITHOUT the tenant's delta (costing only
      accept rate on heavily-adapted tenants) while the verify pass
      scores with it, so output stays token-identical to the tenant's
      own model.
    - ``prefix_cache``: arm radix-tree prefix sharing (False by default —
      the exact PR-8/PR-10 engine). Blocks become content-addressed and
      refcounted; a request whose prompt shares full cached blocks maps
      them read-only, skips their prefill entirely (chunked prefill
      starts at the divergence point) and copy-on-write forks before any
      write into a shared page; the pool evicts leaf-first by LRU when
      the free list runs dry. See serve/prefix_cache.py + doc/serving.md.
    - ``guard``: ``TraceGuard`` action on a signature leak ("raise"/"warn").
    - ``metrics``: arm the typed metrics registry (True for a fresh
      :class:`~dmlcloud_tpu.telemetry.metrics_registry.MetricsRegistry`,
      or pass one to share). Series handles resolve at construction
      (DML215); :meth:`metrics_text` exposes Prometheus text. Off (None)
      by default — the uninstrumented hot loop is untouched.
    - ``slos``: declarative objectives
      (:class:`~dmlcloud_tpu.serve.slo.SLO` list) evaluated every step
      over the injectable clock; burn-rate alerts journal as
      ``slo_alert`` spans and surface in the ledger summary + drain
      verdict (doc/observability.md).
    """

    def __init__(
        self,
        model,
        params: Any,
        *,
        num_blocks: int | None = None,
        block_size: int = 16,
        max_slots: int = 8,
        prefill_chunk: int = 32,
        batch_buckets=None,
        table_buckets=None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        eos_id: int = -1,
        spec_k: int = 0,
        draft_model=None,
        draft_params: Any = None,
        draft_num_blocks: int | None = None,
        medusa_k: int = 0,
        medusa_heads: Any = None,
        adapters: AdapterSet | None = None,
        prefix_cache: bool = False,
        rng: jax.Array | None = None,
        guard: str = "raise",
        cache_dtype: Any = None,
        max_waiting: int | None = None,
        shed_policy: str = "reject",
        fairness: str = "fifo",
        drr_quantum: int | None = None,
        clock: Callable[[], float] = time.perf_counter,
        run_dir: Any = None,
        drain_budget_s: float = 5.0,
        preemption=None,
        watchdog=None,
        max_done: int | None = None,
        ledger_max_records: int | None = None,
        metrics: Any = None,
        slos: Any = None,
        verify: str | None = None,
        hbm_budget: int | None = None,
    ):
        from ..compile.cache import configure_cache
        from ..models.quant import prepare_decode_params

        # before the engine's first compile: a restarted server loads its
        # (batch x table)-bucket programs instead of rebuilding them
        configure_cache()
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if medusa_k < 0:
            raise ValueError(f"medusa_k must be >= 0, got {medusa_k}")
        if spec_k and medusa_k:
            raise ValueError("spec_k and medusa_k are mutually exclusive decode modes")
        if (draft_model is None) != (draft_params is None):
            raise ValueError("draft_model and draft_params must be passed together")
        if draft_model is not None and spec_k < 1:
            raise ValueError("a draft model needs spec_k >= 1")
        if medusa_heads is not None and medusa_k < 1:
            raise ValueError("medusa_heads need medusa_k >= 1")
        self.model = model
        cfg = model.cfg
        cfg.require_attention_only("ServeEngine")
        # one-time host-side preparation: int8 kernels stay fused-quantized
        # and the off-TPU GEMM-operand widen is pre-paid (models/quant.py)
        self.params = prepare_decode_params(params, cfg.dtype)
        self.spec_k = int(spec_k)
        max_table = -(-cfg.max_seq_len // block_size)
        if num_blocks is None:
            num_blocks = max_slots * max_table
        self.pool = KVBlockPool.for_model(
            cfg, num_blocks=num_blocks, block_size=block_size, dtype=cache_dtype
        )
        self.draft_model = None
        self.draft_params = None
        self.draft_pool = None
        if self.spec_k:
            # shared-model self-draft unless a real draft is named; either
            # way the draft owns its OWN page pool — rollback is a fill
            # counter, never shared pages
            self.draft_model = draft_model if draft_model is not None else model
            dparams = draft_params if draft_params is not None else params
            self.draft_params = prepare_decode_params(dparams, self.draft_model.cfg.dtype)
            self.draft_pool = KVBlockPool.for_model(
                self.draft_model.cfg,
                num_blocks=int(draft_num_blocks or num_blocks),
                block_size=block_size,
                dtype=cache_dtype,
            )
        self.medusa_k = int(medusa_k)
        self.medusa_heads = None
        if self.medusa_k:
            # Medusa mode: NO draft model, NO draft pool, NO draft prefill
            # mirror — k-1 extra decode heads ride the target's own forward.
            # Default heads (none passed) are fresh zero-residual blocks
            # warm-started from the base lm_head: correct but untrained
            # (accept rate ~= self-agreement); callers distil real ones.
            from ..models.speculative import init_medusa_heads

            if medusa_heads is not None:
                self.medusa_heads = jax.tree.map(jnp.asarray, medusa_heads)
            else:
                kernel = None
                raw = params.get("lm_head") if hasattr(params, "get") else None
                if raw is not None and not cfg.tie_embeddings:
                    kernel = raw.get("kernel")
                self.medusa_heads = init_medusa_heads(
                    cfg, self.medusa_k, jax.random.PRNGKey(0), lm_head_kernel=kernel
                )
        # prefix sharing: the radix tree lives over the TARGET pool only —
        # the draft pool has no tree (draft prefill skips via the target's
        # match length; the verifier guarantees token identity regardless)
        self.prefix = PrefixCache(self.pool) if prefix_cache else None
        self.scheduler = Scheduler(
            self.pool, max_slots, prefill_chunk,
            draft_pool=self.draft_pool, lookahead=self.spec_k or self.medusa_k,
            prefix_cache=self.prefix,
            max_waiting=max_waiting, shed_policy=shed_policy,
            fairness=fairness, drr_quantum=drr_quantum,
        )
        self.ledger = ServeLedger(max_records=ledger_max_records)
        self.adapters = adapters
        self.eos_id = int(eos_id)
        self._temperature = float(temperature)
        self._top_k = int(top_k)
        self._top_p = float(top_p)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._calls = 0
        self._next_id = 0
        self._done: dict[int, _Sequence] = {}
        # idempotency: accepted caller tokens -> rid (dedup for router
        # retries after ambiguous failures); evicts with retention
        self._tokens: dict[str, int] = {}
        # lifecycle state: every known sequence by id (live + retained
        # terminal), terminal ids in finish order (the retention bound),
        # the injectable clock the whole loop reads, and drain/fault knobs
        self._all: dict[int, _Sequence] = {}
        self._terminal: collections.deque[int] = collections.deque()
        self._max_done = None if max_done is None else int(max_done)
        self.clock = clock
        self.run_dir = run_dir
        self.drain_budget_s = float(drain_budget_s)
        self.preemption = preemption
        self.watchdog = watchdog
        #: chaos hook: ``fn(point, seqs)`` called at "step" (must not
        #: raise) and before each device phase ("prefill"/"decode"/
        #: "draft"/"verify" — the fused Medusa round fires "verify" —
        #: where raising injects a fault) — serve/chaos.py
        self.fault_injector: Callable[[str, Any], None] | None = None
        self._drain_reason: str | None = None
        self._drain_kind = "completed"
        self._drain_requeue = False
        self._drain_started: float | None = None

        # -- observability plane (doc/observability.md) -------------------
        # metrics: every series handle is resolved ONCE here — the hot
        # loop only ever touches pre-bound children (one float add per
        # event; a per-request labels() call is lint rule DML215)
        self.metrics = None
        if metrics:
            from ..telemetry.metrics_registry import (
                ITL_BUCKETS, QUEUE_DEPTH_BUCKETS, TTFT_BUCKETS, MetricsRegistry,
            )
            from .scheduler import TERMINAL_STATUSES

            reg = metrics if isinstance(metrics, MetricsRegistry) else MetricsRegistry()
            self.metrics = reg
            self._m_requests = reg.counter(
                "dml_serve_requests_total", "requests submitted")
            self._m_tokens = reg.counter(
                "dml_serve_tokens_total", "tokens emitted (all requests)")
            terminal = reg.counter(
                "dml_serve_terminal_total", "terminal statuses",
                labels=("status",), max_series=len(TERMINAL_STATUSES) + 1)
            self._m_terminal = {s: terminal.labels(status=s) for s in TERMINAL_STATUSES}
            self._m_drafted = reg.counter(
                "dml_serve_drafted_tokens_total", "speculative tokens proposed")
            self._m_accepted = reg.counter(
                "dml_serve_accepted_tokens_total", "speculative tokens accepted")
            self._m_ttft = reg.histogram(
                "dml_serve_ttft_seconds", "time to first token",
                buckets=TTFT_BUCKETS)
            self._m_itl = reg.histogram(
                "dml_serve_itl_seconds", "inter-token latency",
                buckets=ITL_BUCKETS)
            self._m_depth = reg.histogram(
                "dml_serve_queue_depth", "admission queue depth per step",
                buckets=QUEUE_DEPTH_BUCKETS)
            self._m_batch = reg.gauge(
                "dml_serve_decode_batch_size", "rows in the last decode batch")
            self._m_active = reg.gauge(
                "dml_serve_active_requests", "admitted, unfinished requests")
            self._m_free = reg.gauge(
                "dml_serve_kv_blocks_free", "free blocks in the target pool")
            self._m_live = reg.gauge(
                "dml_serve_kv_blocks_live", "live blocks in the target pool")
            self._m_shared = reg.gauge(
                "dml_serve_kv_blocks_shared", "refcount>1 blocks (prefix sharing)")
            self._m_pref_lookups = reg.counter(
                "dml_serve_prefix_lookups_total", "prefix-cache lookups at admission")
            self._m_pref_hits = reg.counter(
                "dml_serve_prefix_hits_total", "admissions with a cached prefix")
            self._m_pref_saved = reg.counter(
                "dml_serve_prefill_tokens_saved_total",
                "prefill tokens skipped via the prefix cache")
        # SLOs: declarative objectives over the SAME injectable clock
        self.slo = None
        if slos:
            from .slo import SLOMonitor

            self.slo = slos if isinstance(slos, SLOMonitor) else SLOMonitor(
                slos, clock=clock
            )
            # the summary's "slo" section reads the live monitor
            self.ledger.slo_monitor = self.slo

        self.batch_buckets = (
            resolve_buckets(batch_buckets) if batch_buckets else _pow2_buckets(max_slots)
        )
        table_cap = min(max_table, self.pool.num_blocks)
        self.table_buckets = (
            resolve_buckets(table_buckets) if table_buckets else _pow2_buckets(table_cap)
        )
        n_bb, n_tb = len(self.batch_buckets), len(self.table_buckets)
        # per-engine jit: jax keys its trace cache on the function OBJECT,
        # so a fresh partial per engine gives each engine its own cache —
        # the TraceGuard budget is then this engine's alone, not the
        # process-wide total across every engine ever built. The partial
        # takes the function's name, so the profile's module reads
        # ``jit__paged_step`` (a bare partial is ``jit__unknown``)
        def _guarded(fn, budget, name, donate=(0,), statics=None):
            if statics is None:
                statics = ("model",) + (("k",) if fn is not _paged_step else ())
            return TraceGuard(
                jax.jit(
                    functools.update_wrapper(functools.partial(fn), fn),
                    static_argnames=statics,
                    donate_argnums=donate,
                ),
                max_traces=budget, action=guard, name=name,
            )

        # the ONE signature-budget formula (signature_budget below) — the
        # TraceGuard arms here and the DML605 verify check both consume it
        budgets = self.signature_budget(
            n_bb, n_tb,
            spec=bool(self.spec_k), medusa=bool(self.medusa_k),
            prefix_cache=self.prefix is not None,
        )
        self._step_budget = budgets["step"]
        self.max_signatures = budgets["total"]
        if self.spec_k:
            self._spec_budget = budgets["spec"]
            self._draft_fn = _guarded(_spec_draft_step, self._spec_budget, "serve_spec_draft")
            self._verify_fn = _guarded(_spec_verify_step, self._spec_budget, "serve_spec_verify")
        elif self.medusa_k:
            self._medusa_budget = budgets["medusa"]
            self._draft_fn = self._verify_fn = None
            self._medusa_fn = _guarded(_medusa_step, self._medusa_budget, "serve_medusa_step")
        else:
            self._draft_fn = self._verify_fn = None
        if not self.medusa_k:
            self._medusa_fn = None
        self._step_fn = _guarded(_paged_step, self._step_budget, "serve_paged_step")
        self._copy_fn = None
        if self.prefix is not None:
            # COW fork: traced src/dst -> ONE signature for every fork the
            # engine ever performs (counted in the budget)
            self._copy_fn = _guarded(_copy_block, 1, "serve_cow_copy", statics=())

        if verify not in (None, "warn", "error"):
            raise ValueError(f'verify must be None, "warn" or "error", got {verify!r}')
        self._verify_mode = verify
        self.hbm_budget = None if hbm_budget is None else int(hbm_budget)
        #: findings of the construction-time verify preflight (if armed)
        self.verify_findings: list = []
        if verify:
            self._run_verify_preflight(verify)

    @staticmethod
    def signature_budget(
        n_batch_buckets: int,
        n_table_buckets: int,
        *,
        spec: bool = False,
        medusa: bool = False,
        prefix_cache: bool = False,
    ) -> dict:
        """THE signature-budget formula — every compiled signature a healthy
        engine can legitimately own, by decode mode. The constructor's
        TraceGuard arms and the DML605 verify check both read this one
        function, asserted equal to the historical per-mode math by
        ``tests/test_verify.py`` — so the budget can never again drift
        between the runtime guard and the static check.

        Returns ``{"step", "spec", "medusa", "copy", "total"}``:

        - plain decode: ``step`` is (batch bucket x table bucket) decode
          plus (1, chunk) x table-bucket prefill — ``n_bb*n_tb + n_tb``.
        - spec mode: prefill doubles (target + draft mirror through
          ``_paged_step``: ``2*n_tb``) and plain decode stays as the
          degraded-round fallback (``n_bb*n_tb``); each healthy round adds
          one draft + one verify signature per (batch x table) bucket —
          ``spec = n_bb*n_tb``, counted twice in ``total``.
        - Medusa mode: target-only prefill (no draft mirror), the plain
          decode fallback, and ONE fused propose+verify signature per
          (batch x table) bucket — ``medusa = n_bb*n_tb``.
        - ``prefix_cache`` adds the single traced COW-copy signature.
        """
        n_bb, n_tb = int(n_batch_buckets), int(n_table_buckets)
        if spec and medusa:
            raise ValueError("spec and medusa are mutually exclusive decode modes")
        if spec:
            step, spec_b, medusa_b = 2 * n_tb + n_bb * n_tb, n_bb * n_tb, 0
            total = step + 2 * spec_b
        elif medusa:
            step, spec_b, medusa_b = n_bb * n_tb + n_tb, 0, n_bb * n_tb
            total = step + medusa_b
        else:
            step, spec_b, medusa_b = n_bb * n_tb + n_tb, 0, 0
            total = step
        copy = 1 if prefix_cache else 0
        return {"step": step, "spec": spec_b, "medusa": medusa_b, "copy": copy,
                "total": total + copy}

    def _enumerate_signature_surface(self) -> int:
        """Count every signature this engine can legitimately compile by
        EXPLICIT per-bucket enumeration — deliberately NOT a call into
        :meth:`signature_budget`, so the DML605 preflight compares two
        independent derivations and catches either one drifting."""
        surface = 0
        for _tb in self.table_buckets:
            surface += 1  # target prefill: (1, chunk) x this table bucket
            if self.spec_k:
                surface += 1  # draft prefill mirror through _paged_step
        for _bb in self.batch_buckets:
            for _tb in self.table_buckets:
                surface += 1  # plain decode (spec/medusa degraded fallback)
                if self.spec_k:
                    surface += 2  # one draft + one verify per healthy round
                if self.medusa_k:
                    surface += 1  # the fused propose+verify round
        if self.prefix is not None:
            surface += 1  # the traced COW copy
        return surface

    def _run_verify_preflight(self, mode: str) -> None:
        """Construction-time IR verify (doc/lint.md DML6xx): stage the
        worst-case (max batch bucket x max table bucket) decode step on
        CPU and audit its donation contract, baked-in host callbacks and
        memory estimate against ``hbm_budget``, plus the DML605 check
        that the enumerated signature surface fits ``max_signatures``.
        AOT lower/compile never touches the jit dispatch cache, so the
        TraceGuard budgets are unaffected. ``"warn"`` emits a warning
        with the findings; ``"error"`` raises :class:`LintError`."""
        import warnings

        from ..lint import LintError
        from ..lint import ir as ir_mod

        bb = max(self.batch_buckets)
        tb = max(self.table_buckets)
        specs = [
            ir_mod.ProgramSpec(
                name="serve.signature_surface",
                fn=None,
                signature_surface=self._enumerate_signature_surface(),
                signature_budget=self.max_signatures,
                kind="serve",
            ),
            ir_mod.ProgramSpec(
                name=f"serve.paged_step[b{bb}xt{tb}]",
                fn=self._step_fn._fn,
                args=self._paged_step_specs(bb, tb, 1, adapters=False),
                static_kwargs={"model": self.model},
                donate_argnums=(0,),
                hbm_budget_bytes=self.hbm_budget,
                kind="serve",
            ),
        ]
        stats: dict = {}
        findings = ir_mod.verify_programs(specs, stats=stats)
        self.verify_findings = list(findings)
        if not findings:
            return
        report = "\n".join(f.format() for f in findings)
        msg = (
            f"IR verifier found {len(findings)} problem(s) in the serve step "
            f"programs (doc/lint.md DML6xx; suppress with "
            f"'# dmllint: disable=ID'):\n{report}"
        )
        if mode == "error":
            raise LintError(msg, findings=findings)
        warnings.warn(msg, stacklevel=3)

    def _paged_step_specs(self, bb: int, tb: int, tokens: int, adapters: bool = True) -> tuple:
        """The abstract arguments of one ``_paged_step`` signature: ``bb`` rows
        of ``tokens`` tokens over ``tb`` table entries."""
        from ..compile import aot

        sds = jax.ShapeDtypeStruct
        f32, i32 = jnp.float32, jnp.int32
        lora = None
        if adapters and self.adapters is not None:
            lora = (aot.abstract_spec(self.adapters.stacked), sds((bb,), i32))
        return (
            aot.abstract_spec(self.pool.pools),
            aot.abstract_spec(self.params),
            sds((bb, tb), i32),       # block tables
            sds((bb,), i32),          # fill
            sds((bb, tokens), i32),   # tokens
            sds((bb,), i32),          # last_idx
            aot.abstract_spec(self._rng),
            lora,
            sds((bb,), f32),          # temperature
            sds((bb,), i32),          # top_k
            sds((bb,), f32),          # top_p
        )

    def phase_map(self, batch_bucket: int, table_bucket: int, *, prefill: bool = False) -> dict:
        """``{instruction: (phase, direction)}`` (``utils.profiling.phase_map``)
        of one compiled ``_paged_step`` signature: a decode batch of
        ``batch_bucket`` rows over ``table_bucket`` table entries or, with
        ``prefill``, ``batch_bucket`` rows of one prefill chunk. The engine
        compiles lazily and keeps no executable, so the signature is lowered
        and compiled here, on demand: a whole compile the first time (the
        ahead-of-time path does not share the jitted call's cache entry), a
        compile-cache hit after. For an operator reading a profile of this
        engine, never inside ``step()``. It adds nothing to
        ``compiled_signatures()``."""
        from ..utils.profiling import phase_map

        tokens = self.scheduler.prefill_chunk if prefill else 1
        specs = self._paged_step_specs(int(batch_bucket), int(table_bucket), tokens)
        return phase_map(self._step_fn._fn.lower(*specs, model=self.model).compile())

    # -- request lifecycle ---------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int = 32,
        adapter: str | None = None,
        *,
        temperature: float | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
        eos_id: int | None = None,
        deadline_s: float | None = None,
        priority: int = 0,
        tenant: str | None = None,
        token: str | None = None,
        trace: str | None = None,
        arrival: float | None = None,
    ) -> int:
        """Queue one request; returns its id. ``prompt`` is a 1-D int32
        token sequence (no padding — paged rows sit at their own absolute
        positions, ragged prompts are the natural case). The sampling
        knobs override the engine defaults FOR THIS REQUEST ONLY — they
        are data to the compiled step, so a batch may mix greedy and
        sampled tenants freely.

        ``deadline_s`` is a budget relative to NOW; a request that has
        not finished when it elapses terminates ``deadline_exceeded`` at
        whatever phase it is in. ``priority`` matters only to shed-victim
        selection under overload (lower sheds first). ``tenant`` keys the
        fairness scheduler (default: the adapter name, else one shared
        tenant). Submission can itself shed — the returned id's status
        may already be ``shed`` when the bounded queue chose the arrival
        as the victim.

        ``token`` is an optional caller-supplied idempotency token: a
        token the engine has already accepted raises
        :class:`DuplicateRequest` (carrying the original rid) instead of
        admitting a second copy — the at-most-once guard a router retry
        leans on after an AMBIGUOUS failure (did the dead replica's
        submit land before it died?). Tokens age out with the terminal-
        record retention (``max_done``).

        ``trace`` is the request-scoped trace id every span this request
        produces links under (doc/observability.md). A router mints one
        at ``Router.submit`` and threads it through failover, so the
        whole cross-replica history is ONE causal trace; a standalone
        engine mints ``tr-<rid>`` when none is given.

        ``arrival`` is when the request was due, on the engine's clock, for
        a caller that held it in a queue of its own before this call (a
        router, an open-loop load generator): the ledger's ``arrival``, the
        ``queue_wait`` span and TTFT count from it. Default: now.
        ``deadline_s`` stays a budget from this call."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        # spec/medusa rounds may write up to k proposals past the final
        # committed slot (plus the bonus slot) — the same slack
        # speculative_generate reserves; plain decode keeps the PR-8 bound
        lookahead = self.spec_k or self.medusa_k
        slack = lookahead + 1 if lookahead else 0
        if prompt.size + int(max_new_tokens) + slack > self.model.cfg.max_seq_len:
            knob = "spec_k" if self.spec_k else "medusa_k"
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens})"
                + (f" + {knob}+1 ({slack})" if slack else "")
                + f" exceeds max_seq_len ({self.model.cfg.max_seq_len})"
            )
        aid = 0
        if adapter is not None:
            if self.adapters is None:
                raise ValueError("request names an adapter but the engine has no AdapterSet")
            aid = self.adapters.id_of(adapter)
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if token is not None and token in self._tokens:
            raise DuplicateRequest(token, self._tokens[token])
        now = self.clock()
        if arrival is None:
            arrival = now
        elif arrival > now:
            raise ValueError(f"arrival ({arrival}) lies after the engine's clock ({now})")
        rid = self._next_id
        self._next_id += 1
        if rid in self._all:  # a reused rid would silently clobber bookkeeping
            raise RuntimeError(f"request id {rid} already exists (corrupt id counter)")
        if trace is None:
            trace = f"tr-{rid}"
        if self.metrics is not None:
            self._m_requests.inc()
        req = Request(
            prompt=prompt, max_new_tokens=int(max_new_tokens), adapter=adapter,
            temperature=temperature, top_k=top_k, top_p=top_p, eos_id=eos_id,
            deadline_s=deadline_s, priority=int(priority), tenant=tenant, id=rid,
        )
        resolved_tenant = tenant if tenant is not None else (adapter or "")
        seq = _Sequence(
            req=req, arrival=float(arrival), adapter_id=aid,
            deadline=None if deadline_s is None else now + float(deadline_s),
            tenant=resolved_tenant, priority=int(priority), token=token, trace=trace,
            temperature=self._temperature if temperature is None else float(temperature),
            top_k=self._top_k if top_k is None else int(top_k),
            top_p=self._top_p if top_p is None else float(top_p),
            eos_id=self.eos_id if eos_id is None else int(eos_id),
        )
        if self.draining:
            # drain contract: admission is closed — arrivals shed on sight
            self.ledger.arrived(rid, seq.arrival, tenant=resolved_tenant)
            self._all[rid] = seq
            if token is not None:
                self._tokens[token] = rid
            self._finalize(seq, now, "shed")
            return rid
        shed = self.scheduler.submit(seq)  # validates; raising records nothing
        self.ledger.arrived(rid, seq.arrival, tenant=resolved_tenant)
        self._all[rid] = seq
        if token is not None:
            self._tokens[token] = rid
        for victim in shed:
            # bounded-queue overflow: the scheduler picked the victim but
            # the engine owns its terminal bookkeeping (it may be ``seq``
            # itself, never enqueued, or a queued request holding nothing)
            self._finalize(victim, now, "shed")
        return rid

    def output(self, rid: int) -> np.ndarray:
        """The emitted tokens of a finished request."""
        return np.asarray(self._done[rid].out, np.int32)

    def results(self) -> dict[int, np.ndarray]:
        return {rid: self.output(rid) for rid in self._done}

    def cancel(self, rid: int) -> bool:
        """Cancel a live request at WHATEVER phase it is in — queued,
        mid-chunked-prefill, mid-decode, mid-spec-round. Its blocks (both
        pools), COW spare and prefix locks release immediately; status
        becomes ``cancelled``. Returns False when the request is unknown
        or already terminal (cancellation lost the race — idempotent, no
        double-free)."""
        seq = self._all.get(rid)
        if seq is None or seq.status is not None:
            return False
        return self._finalize(seq, self.clock(), "cancelled")

    def status(self, rid: int) -> str:
        """The request's phase: ``queued`` / ``running`` while live, else
        its terminal status (``ok | cancelled | deadline_exceeded | shed
        | error``)."""
        seq = self._all.get(rid)
        if seq is None:
            raise KeyError(f"unknown (or retention-evicted) request id {rid}")
        if seq.status is not None:
            return seq.status
        return "queued" if seq.admitted is None else "running"

    def statuses(self) -> dict[int, str]:
        """Every retained request's :meth:`status`, by id."""
        return {rid: self.status(rid) for rid in self._all}

    # -- terminal bookkeeping ------------------------------------------------
    def _finalize(self, seq, now: float, status: str, error: str | None = None) -> bool:
        """The engine half of the ONE exit path: scheduler terminate
        (queue removal + every block released), then ledger/journal/
        retention. False when already terminal (idempotent)."""
        if not self.scheduler.terminate(seq, now, status):
            return False
        self._record_terminal(seq, now, error)
        return True

    def _record_terminal(self, seq, now: float, error: str | None = None) -> None:
        rid = seq.req.id
        self.ledger.finished(rid, now, status=seq.status)
        if self.metrics is not None:
            child = self._m_terminal.get(seq.status)
            if child is not None:
                child.inc()
        if self.slo is not None:
            self.slo.record_terminal(seq.tenant, seq.status, now)
        if seq.status == "error":
            # the per-request fault span stamps the trace with its
            # terminal status — a chaos-injected failure is readable
            # straight off the request track
            journal.emit("fault", now, label=f"req{rid}", request=rid,
                         trace=seq.trace, status=seq.status, error=error or "")
        if seq.status == "ok":
            self._done[rid] = seq
        self._terminal.append(rid)
        if self._max_done is not None:
            while len(self._terminal) > self._max_done:
                old = self._terminal.popleft()
                self._done.pop(old, None)
                dropped = self._all.pop(old, None)
                if dropped is not None and dropped.token is not None:
                    self._tokens.pop(dropped.token, None)

    def _fail(self, seqs, exc: BaseException) -> None:
        """Isolate a step failure to the request(s) it was advancing:
        status ``error``, every resource released, everyone else keeps
        serving."""
        now = self.clock()
        msg = f"{type(exc).__name__}: {exc}"
        for s in seqs:
            self._finalize(s, now, "error", error=msg)

    @property
    def idle(self) -> bool:
        return self.scheduler.idle

    def compiled_signatures(self) -> int | None:
        """Distinct compiled signatures so far, summed over the engine's
        jitted steps (the TraceGuard probes)."""
        total = 0
        for fn in (self._step_fn, self._draft_fn, self._verify_fn,
                   self._medusa_fn, self._copy_fn):
            if fn is None:
                continue
            n = fn.cache_size()
            if n is None:
                return None
            total += n
        return total

    # -- the serving loop ----------------------------------------------------
    def step(self) -> bool:
        """One engine iteration: expire deadlines, admit (or drain), one
        prefill chunk, one decode batch (a speculative round when
        ``spec_k``, a Medusa round when ``medusa_k``). Returns whether
        any device work ran. A failure in
        either device phase is isolated to the request(s) it was
        advancing — the step itself never raises for a per-request
        fault. With a journal armed the whole iteration is one
        ``engine_step`` span: its bookkeeping is that span minus the
        ``call_build`` and device-call spans inside it."""
        j = journal.active_journal()
        if j is None:
            return self._step()
        t0 = journal.now()
        try:
            return self._step()
        finally:
            j.emit("engine_step", t0)

    def _step(self) -> bool:
        now = self.clock()
        if self.watchdog is not None:
            self.watchdog.notify()
        self._chaos("step", None)
        for seq in self.scheduler.expire(now):
            # the scheduler already terminated them (blocks released);
            # the engine owns the ledger/journal tail
            self._record_terminal(seq, now)
        if (
            self.preemption is not None
            and self.preemption.triggered
            and not self.draining
        ):
            self.request_drain(
                f"preemption:{self.preemption.signal_name}",
                kind="preemption", requeue=True,
            )
        if self.draining:
            self._drain_step(now)
        else:
            j = journal.active_journal()
            for seq in self.scheduler.admit(now):
                rid = seq.req.id
                self.ledger.admitted(rid, now)
                if self.prefix is not None:
                    # prefill-skip accounting: saved = the divergence point the
                    # scheduler rolled prefill forward to (cached tokens, minus
                    # the one re-fed token of an exact full-block match)
                    self.ledger.prefix_match(
                        rid, cached=seq.cached_tokens, saved=seq.fill,
                        prompt=seq.prompt_len,
                    )
                    if j is not None:
                        j.emit("prefix_lookup", now, now, label=f"req{rid}",
                               request=rid, trace=seq.trace,
                               cached=seq.cached_tokens, saved=seq.fill,
                               shared=seq.shared)
                    if self.metrics is not None:
                        self._m_pref_lookups.inc()
                        if seq.cached_tokens > 0:
                            self._m_pref_hits.inc()
                        self._m_pref_saved.inc(seq.fill)
                if j is not None:
                    j.emit("queue_wait", seq.arrival, now, label=f"req{rid}",
                           request=rid, trace=seq.trace,
                           depth=self.scheduler.depth())
                    j.emit("admission", now, now, label=f"req{rid}",
                           request=rid, trace=seq.trace, tenant=seq.tenant,
                           blocks=len(seq.blocks), cached=seq.cached_tokens)
        if self.metrics is not None:
            self._m_depth.observe(self.scheduler.depth())
            self._m_active.set(self.scheduler.active)
            self._m_free.set(self.pool.num_free)
            self._m_live.set(self.pool.num_live)
        if self.slo is not None:
            self.slo.evaluate(now)
        did = False
        seq = self.scheduler.next_prefill()
        if seq is not None:
            try:
                self._prefill_chunk(seq)
            except Exception as exc:  # noqa: BLE001 — isolate to this request
                self._fail([seq], exc)
            did = True
        batch = self.scheduler.decode_batch()
        if batch:
            try:
                if self.spec_k:
                    self._decode_spec(batch)
                elif self.medusa_k:
                    self._decode_medusa(batch)
                else:
                    self._decode(batch)
            except Exception as exc:  # noqa: BLE001 — isolate to these rows
                self._fail(batch, exc)
            did = True
        return did

    def _chaos(self, point: str, seqs) -> None:
        if self.fault_injector is not None:
            self.fault_injector(point, seqs)

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Drive :meth:`step` until every submitted request finished (or
        ``max_steps`` elapsed); returns the finished outputs."""
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.results()

    # -- graceful drain ------------------------------------------------------
    @property
    def draining(self) -> bool:
        return self._drain_reason is not None

    def request_drain(
        self, reason: str = "drain requested", *,
        kind: str = "completed", requeue: bool = False,
    ) -> None:
        """Begin graceful drain: admission closes (arrivals shed on
        sight, the waiting queue sheds next step), in-flight requests get
        ``drain_budget_s`` from now to finish, then shed too. First call
        wins; later calls are no-ops."""
        if self._drain_reason is None:
            self._drain_reason = str(reason)
            self._drain_kind = kind
            self._drain_requeue = bool(requeue)
            self._drain_started = self.clock()

    def _drain_step(self, now: float) -> None:
        for seq in list(self.scheduler.iter_waiting()):
            self._finalize(seq, now, "shed")
        if now - self._drain_started >= self.drain_budget_s:
            # budget spent: in-flight work sheds, blocks release, the
            # verdict reports what was cut short
            for seq in [*self.scheduler.prefilling, *self.scheduler.running]:
                self._finalize(seq, now, "shed")

    def drain(self, reason: str | None = None, *, kind: str | None = None,
              requeue: bool | None = None, max_steps: int | None = None) -> dict:
        """Drain to completion and write the ``requeue.json`` verdict:
        stop admission, shed the queue, step until in-flight work
        finishes (or the drain budget sheds it), then record the verdict
        under ``run_dir`` (skipped when the engine has none) — the same
        schema every elasticity wrapper reads (doc/elasticity.md).
        Defaults: a tripped ``PreemptionGuard`` makes this a
        ``kind="preemption"``, ``requeue=True`` verdict; a manual drain
        is ``kind="completed"``, no requeue. Returns the verdict dict."""
        if not self.draining:
            preempted = self.preemption is not None and self.preemption.triggered
            if reason is None:
                reason = (
                    f"preemption:{self.preemption.signal_name}" if preempted
                    else "drain requested"
                )
            self.request_drain(
                reason,
                kind=kind or ("preemption" if preempted else "completed"),
                requeue=(preempted if requeue is None else requeue),
            )
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        now = self.clock()
        counts = self.ledger.status_counts()
        verdict = {
            "requeue": self._drain_requeue,
            "kind": self._drain_kind,
            "reason": self._drain_reason,
            "serve": {
                "drain_s": round(now - self._drain_started, 6),
                "statuses": counts,
                "drained_clean": self.idle,
                "slo_alerts": len(self.slo.alerts) if self.slo is not None else 0,
            },
        }
        journal.emit("drain", self._drain_started, now, label=self._drain_kind,
                     **counts)
        if self.run_dir is not None:
            from ..checkpoint import write_requeue_verdict

            write_requeue_verdict(
                self.run_dir, verdict["requeue"], verdict["reason"],
                verdict["kind"], serve=verdict["serve"],
            )
        return verdict

    def leaked_blocks(self) -> int:
        """Blocks still live once the engine is idle beyond what the
        prefix tree legitimately holds (one reference per cached node),
        plus any excess lock references on tree blocks — the chaos
        drill's zero-leak observable. Only meaningful when :attr:`idle`."""
        held = self.prefix.stats()["nodes"] if self.prefix is not None else 0
        leaked = self.pool.num_live - held
        if self.draft_pool is not None:
            leaked += self.draft_pool.num_live
        if self.prefix is not None:
            leaked += len(self.prefix.leaked_locks())
        return leaked

    def metrics_text(self) -> str:
        """The engine's metrics registry rendered as Prometheus text
        (empty string when constructed without ``metrics=``). Pool
        occupancy gauges are refreshed at scrape time so an idle engine
        still reports truthful numbers; wire this to
        :class:`~dmlcloud_tpu.serve.metrics_http.MetricsServer` (or any
        scraper) — a scrape never touches device state."""
        snap = self.metrics_snapshot()
        if snap is None:
            return ""
        from ..telemetry.metrics_registry import to_prometheus_text

        return to_prometheus_text(snap)

    def metrics_snapshot(self) -> dict | None:
        """Gauge-refreshed registry snapshot (plain dicts; None when
        metrics are off) — what :meth:`metrics_text` renders and what the
        router merges across replicas under a ``replica`` label."""
        if self.metrics is None:
            return None
        self._m_free.set(self.pool.num_free)
        self._m_live.set(self.pool.num_live)
        self._m_shared.set(self.pool.stats()["shared"])
        self._m_active.set(self.scheduler.active)
        return self.metrics.snapshot()

    def serve_trace(self, trace, clock=None, sleep=time.sleep) -> dict:
        """Replay a timed request trace in real time: ``trace`` is a list
        of ``(offset_s, prompt, max_new_tokens[, adapter_or_kwargs])``
        tuples (offsets relative to the replay start; the optional last
        element is an adapter name, or a dict of extra :meth:`submit`
        keywords — ``tenant``/``deadline_s``/``priority``/sampling).
        Requests are submitted when the wall reaches their offset; the
        engine steps continuously in between. ``clock`` defaults to the
        engine's own (injectable) clock. Returns the ledger summary."""
        if clock is None:
            clock = self.clock
        pending = sorted(trace, key=lambda e: e[0])
        t0 = clock()
        i = 0
        while i < len(pending) or not self.idle:
            now = clock() - t0
            while i < len(pending) and pending[i][0] <= now:
                off, prompt, max_new, *rest = pending[i]
                kw = {}
                if rest:
                    kw = dict(rest[0]) if isinstance(rest[0], dict) else {"adapter": rest[0]}
                self.submit(prompt, max_new, **kw)
                i += 1
            if self.draining:
                # drain: admission is closed — drop the unsubmitted tail
                i = len(pending)
            if not self.step() and i < len(pending):
                # idle but the trace has future arrivals: nap until the next
                sleep(min(max(pending[i][0] - (clock() - t0), 0.0), 0.001))
        return self.ledger.summary()

    # -- device calls --------------------------------------------------------
    def _next_rng(self):
        self._calls += 1
        return jax.random.fold_in(self._rng, self._calls)

    def _row_params(self, seqs, bb: int):
        """The per-row sampling-param arrays of a (padded) batch. Pad rows
        get the greedy defaults — their samples are discarded, the values
        only need to keep the traced math finite."""
        temps = np.zeros(bb, np.float32)
        topks = np.zeros(bb, np.int32)
        topps = np.ones(bb, np.float32)
        eos = np.full(bb, -1, np.int32)
        for i, s in enumerate(seqs):
            temps[i] = s.temperature
            topks[i] = s.top_k
            topps[i] = s.top_p
            eos[i] = s.eos_id
        return (
            jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps), jnp.asarray(eos)
        )

    def _call(self, pool, model, params, tables, fill, tokens, last_idx, ids, row_params,
              use_adapters=True):
        """One ``_paged_step`` call: ``(tokens, marks)``. ``marks`` are the
        ``perf_counter`` readings after the uploads, after the launch and
        after the fetch, for :meth:`_emit_call`; with no journal armed they
        are all the call costs."""
        temps, topks, topps, _ = row_params
        adapters = None
        if self.adapters is not None and use_adapters:
            adapters = (self.adapters.stacked, jnp.asarray(ids, jnp.int32))
        args = (
            jnp.asarray(tables, jnp.int32), jnp.asarray(fill, jnp.int32),
            jnp.asarray(tokens, jnp.int32), jnp.asarray(last_idx, jnp.int32),
            self._next_rng(),
        )
        t_uploaded = time.perf_counter()
        tok, new_pools = self._step_fn(
            pool.pools, params, *args, adapters, temps, topks, topps, model=model,
        )
        pool.swap(new_pools)
        t_launched = time.perf_counter()  # dmllint: disable=DML106 -- call_launch IS the enqueue; the fetch below waits
        tok = np.asarray(tok)  # the per-step host sync: tokens ARE the output
        return tok, (t_uploaded, t_launched, time.perf_counter())

    @staticmethod
    def _emit_call(j, kind, t_build, t0, marks, bucket, blocks, label, **attrs) -> None:
        """The spans of one device call, on an armed journal ``j``: the
        call's own ``kind`` span from ``t0`` to the fetch's end, tiled by
        ``call_upload`` (host arrays to the device, the key's fold-in),
        ``call_launch`` (the jitted call returning, the pool swapped) and
        ``call_fetch`` (the tokens back on the host); before it
        ``call_build`` (copy-on-write guards, tables, fills, row
        parameters and their uploads) from ``t_build``."""
        t1, t2, t3 = marks
        shared = {"parent": kind, "bucket": bucket, "blocks": blocks}
        j.emit("call_build", t_build, t0, **shared)
        j.emit(kind, t0, t3, label=label, bucket=bucket, blocks=blocks, **attrs)
        j.emit("call_upload", t0, t1, **shared)
        j.emit("call_launch", t1, t2, **shared)
        j.emit("call_fetch", t2, t3, **shared)

    def _cow_guard(self, seq, lo: int, hi: int) -> None:
        """The copy-on-write fork rule: before ANY paged scatter that will
        write positions ``[lo, hi)`` of ``seq``, fork every covered block
        whose refcount > 1 — a shared page is read-only (other tables map
        it; the radix tree pins it), so the write gets a private copy
        first. The fork consumes the COW spare the scheduler reserved at
        admission (an exact full-block match is the one flow that
        guarantees a fork; see scheduler.admit), falls back to a fresh
        alloc otherwise, device-copies the page through the ONE traced
        ``_copy_block`` signature, swaps the table entry and releases this
        sequence's reference to the shared original. No-op without a
        prefix cache (nothing is ever shared) and on the common decode
        path (writes land past the shared prefix by construction)."""
        if self.prefix is None:
            return
        bs = self.pool.block_size
        for bi in range(lo // bs, (max(hi, lo + 1) - 1) // bs + 1):
            if bi >= len(seq.blocks) or not self.pool.is_shared(seq.blocks[bi]):
                continue
            old = seq.blocks[bi]
            if seq.cow_spare > 0:
                new = seq.blocks.pop()  # the spare reserved at admission
                seq.cow_spare -= 1
            else:
                [new] = self.pool.alloc(1)
            self.pool.swap(
                self._copy_fn(self.pool.pools, jnp.int32(old), jnp.int32(new))
            )
            seq.blocks[bi] = new
            self.pool.release([old])
            seq.shared = min(seq.shared, bi)
            j = journal.active_journal()
            if j is not None:
                j.emit("cow_fork", journal.now(), label=f"req{seq.req.id}:cow",
                       request=seq.req.id, trace=seq.trace, cow_block=bi)

    def _table_rows(self, seqs, nb: int, draft: bool = False) -> np.ndarray:
        pool = self.draft_pool if draft else self.pool
        rows = np.full((len(seqs), nb), pool.sentinel, np.int32)
        for i, s in enumerate(seqs):
            owned = s.draft_blocks if draft else s.blocks
            blocks = owned[: min(len(owned), nb)]
            rows[i, : len(blocks)] = blocks
        return rows

    def _prefill_chunk(self, seq) -> None:
        j = journal.active_journal()  # read once a call: off, no label or list is built
        t_build = journal.now()
        self._chaos("prefill", [seq])
        c = self.scheduler.prefill_chunk
        n = min(c, seq.prompt_len - seq.fill)
        # COW-fork before the scatter: an exact full-block prefix match
        # re-feeds the final prompt token, whose write lands in the last
        # SHARED block (the one write the sharing design ever aims at a
        # refcount>1 page)
        self._cow_guard(seq, seq.fill, seq.fill + n)
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n] = seq.req.prompt[seq.fill : seq.fill + n]
        nb = bucket_for(self.pool.blocks_for(seq.fill + n), self.table_buckets)
        final = seq.fill + n >= seq.prompt_len
        row_params = self._row_params([seq], 1)
        fill = np.asarray([seq.fill], np.int32)
        last = np.asarray([n - 1], np.int32)
        t0 = journal.now()
        tok, marks = self._call(
            self.pool, self.model, self.params,
            self._table_rows([seq], nb), fill, tokens, last,
            [seq.adapter_id], row_params,
        )
        if j is not None:
            self._emit_call(j, "prefill", t_build, t0, marks, 1, nb, f"req{seq.req.id}",
                            request=seq.req.id, trace=seq.trace, chunk=n, fill=seq.fill + n)
        if self.spec_k:
            # the draft pool needs the same prompt K/V: one mirrored chunk
            # through the draft model (its sampled token is discarded)
            t1 = journal.now()
            _, marks = self._call(
                self.draft_pool, self.draft_model, self.draft_params,
                self._table_rows([seq], nb, draft=True), fill, tokens, last,
                [seq.adapter_id], row_params,
                use_adapters=False,  # the draft proposes base-model (spec x LoRA)
            )
            if j is not None:
                self._emit_call(j, "draft", t1, t1, marks, 1, nb, f"req{seq.req.id}:prefill",
                                request=seq.req.id, traces=[seq.trace], chunk=n)
        seq.fill += n
        self.ledger.prefilled(n)
        if final:
            # the last real prompt position's logits ARE the first token —
            # time-to-first-token ends here, before any decode step
            now = self.clock()
            self.ledger.first_token(seq.req.id, now)
            if self.metrics is not None:
                self._m_ttft.observe(now - seq.arrival)
            if self.slo is not None:
                self.slo.record_ttft(seq.tenant, now - seq.arrival, now)
            self.scheduler.prefill_done(seq)
            seq.prev_token = int(seq.req.prompt[-1])
            if self.prefix is not None:
                # the prompt's full blocks now hold correct K/V: publish
                # them so the NEXT request with this prefix skips prefill
                self.prefix.insert(seq.req.prompt, seq.blocks, adapter=seq.adapter_id)
            self._emit(seq, int(tok[0]), now)

    def _decode(self, batch) -> None:
        j = journal.active_journal()  # read once a call: off, no label or list is built
        t_build = journal.now()
        self._chaos("decode", batch)
        for s in batch:
            # refcount check before the scatter (DML211): decode writes at
            # fill, past the shared prefix by construction — a fork here
            # means an invariant broke upstream, but the guard is cheap
            self._cow_guard(s, s.fill, s.fill + 1)
        bb = bucket_for(len(batch), self.batch_buckets)
        needed = max(s.needed_blocks(self.pool.block_size) for s in batch)
        nb = bucket_for(needed, self.table_buckets)
        tables = np.full((bb, nb), self.pool.sentinel, np.int32)
        tables[: len(batch)] = self._table_rows(batch, nb)
        fill = np.zeros(bb, np.int32)
        tokens = np.zeros((bb, 1), np.int32)
        ids = np.zeros(bb, np.int64)
        for i, s in enumerate(batch):
            fill[i] = s.fill
            tokens[i, 0] = s.last_token
            ids[i] = s.adapter_id
        row_params = self._row_params(batch, bb)
        t0 = journal.now()
        tok, marks = self._call(
            self.pool, self.model, self.params, tables, fill, tokens,
            np.zeros(bb, np.int32), ids, row_params,
        )
        now = self.clock()
        if j is not None:
            self._emit_call(j, "decode_batch", t_build, t0, marks, bb, nb, f"b{bb}",
                            active=len(batch), traces=[s.trace for s in batch])
        self.ledger.step_sample(self.scheduler.depth(), len(batch))
        if self.metrics is not None:
            self._m_batch.set(len(batch))
        for i, s in enumerate(batch):
            s.fill += 1  # the fed token's K/V landed at its position
            self._emit(s, int(tok[i]), now)

    def _decode_spec(self, batch) -> None:
        """One speculative round for the whole decode batch: k draft
        passes, one k+1-position verify, then the host commits each row's
        accepted prefix. The partial-accept rewind is exactly the
        ``fill += n_new`` below — fill counters roll forward only to the
        accepted position; the stale speculative K/V past it is
        overwritten by the next round's contiguous writes before the
        causal mask can expose it, and block ownership never changes."""
        k = self.spec_k
        for s in batch:
            # a spec round writes fill..fill+k (verify) — COW/refcount
            # check before the multi-token scatter (DML211)
            self._cow_guard(s, s.fill, s.fill + k + 1)
        bb = bucket_for(len(batch), self.batch_buckets)
        needed = max(
            s.needed_blocks(self.pool.block_size, lookahead=k) for s in batch
        )
        nb = bucket_for(needed, self.table_buckets)
        tables = np.full((bb, nb), self.pool.sentinel, np.int32)
        tables[: len(batch)] = self._table_rows(batch, nb)
        dtables = np.full((bb, nb), self.draft_pool.sentinel, np.int32)
        dtables[: len(batch)] = self._table_rows(batch, nb, draft=True)
        # pad rows: fill=1 keeps every traced position >= 0 and the
        # attention mask non-empty; their sentinel tables drop all writes
        fill = np.ones(bb, np.int32)
        prev = np.zeros(bb, np.int32)
        last = np.zeros(bb, np.int32)
        for i, s in enumerate(batch):
            fill[i] = s.fill
            prev[i] = s.prev_token
            last[i] = s.last_token
        temps, topks, topps, eos = self._row_params(batch, bb)
        adapters = None
        if self.adapters is not None:
            # spec x LoRA: the VERIFY pass scores with each row's adapter
            # (the draft proposed base-model — only accept rate pays)
            ids = np.zeros(bb, np.int32)
            for i, s in enumerate(batch):
                ids[i] = s.adapter_id
            adapters = (self.adapters.stacked, jnp.asarray(ids, jnp.int32))
        tables = jnp.asarray(tables, jnp.int32)
        dtables = jnp.asarray(dtables, jnp.int32)
        fill = jnp.asarray(fill, jnp.int32)
        prev = jnp.asarray(prev, jnp.int32)
        last = jnp.asarray(last, jnp.int32)

        t0 = journal.now()
        try:
            self._chaos("draft", batch)
            proposals, dlogits, dpools = self._draft_fn(
                self.draft_pool.pools, self.draft_params, dtables, fill, prev, last,
                self._next_rng(), temps, topks, topps,
                model=self.draft_model, k=k,
            )
        except Exception as exc:  # noqa: BLE001 — the draft is an optimization
            self._degrade_round(batch, t0, bb, exc)
            return
        self.draft_pool.swap(dpools)
        journal.emit("draft", t0, label=f"b{bb}", active=len(batch),
                     bucket=bb, blocks=nb, k=k, traces=[s.trace for s in batch])
        t1 = journal.now()
        self._chaos("verify", batch)
        packed, tpools = self._verify_fn(
            self.pool.pools, self.params, tables, fill, last, proposals, dlogits,
            self._next_rng(), temps, topks, topps, eos, adapters,
            model=self.model, k=k,
        )
        self.pool.swap(tpools)
        # ONE fetch: tokens and the n_new/n_accept counters ride together
        out = np.asarray(packed)
        now = time.perf_counter()
        journal.emit("verify", t1, label=f"b{bb}", active=len(batch),
                     bucket=bb, blocks=nb, k=k, traces=[s.trace for s in batch])
        self.ledger.step_sample(self.scheduler.depth(), len(batch))
        if self.metrics is not None:
            self._m_batch.set(len(batch))
        for i, s in enumerate(batch):
            n_new = int(out[i, k + 1])
            self.ledger.spec_round(s.req.id, drafted=k, accepted=int(out[i, k + 2]))
            if self.metrics is not None:
                self._m_drafted.inc(k)
                self._m_accepted.inc(int(out[i, k + 2]))
            for tok in out[i, :n_new]:
                prev_last = s.last_token
                s.fill += 1  # this token's K/V was written by the round
                self._emit(s, int(tok), now)
                if s.finished is not None:
                    break
                s.prev_token = prev_last

    def _decode_medusa(self, batch) -> None:
        """One Medusa round for the whole decode batch: ONE model forward
        (``_medusa_step``) verifies the proposals the PREVIOUS round's
        forward emitted — no draft model, no draft pool, no draft tables,
        no propose pass. Each sequence carries its pending proposals as
        ``k-1`` host ints (``seq.medusa_pending``, part of the round's
        single packed fetch); a row's FIRST round after prefill has none
        yet and runs on sentinel proposals (one near-plain round, never a
        correctness cost — the verify rule rejects them). The commit loop
        and partial-accept rewind are exactly the spec-mode ones: fill
        counters roll forward only to the accepted position; stale
        speculative K/V past fill is overwritten by the next round's
        contiguous writes before the causal mask can expose it."""
        k = self.medusa_k
        for s in batch:
            # a round writes fill..fill+k-1 (verify) — COW/refcount check
            # before the multi-token scatter (DML211)
            self._cow_guard(s, s.fill, s.fill + k)
        bb = bucket_for(len(batch), self.batch_buckets)
        needed = max(
            s.needed_blocks(self.pool.block_size, lookahead=k) for s in batch
        )
        nb = bucket_for(needed, self.table_buckets)
        tables = np.full((bb, nb), self.pool.sentinel, np.int32)
        tables[: len(batch)] = self._table_rows(batch, nb)
        # pad rows: fill=1 keeps every traced position >= 0 and the
        # attention mask non-empty; their sentinel tables drop all writes
        fill = np.ones(bb, np.int32)
        last = np.zeros(bb, np.int32)
        prop = np.zeros((bb, max(k - 1, 0)), np.int32)
        for i, s in enumerate(batch):
            fill[i] = s.fill
            last[i] = s.last_token
            pending = getattr(s, "medusa_pending", None)
            if pending is not None and k > 1:
                prop[i] = pending
        temps, topks, topps, eos = self._row_params(batch, bb)
        adapters = None
        if self.adapters is not None:
            # medusa x LoRA: ONE model means the heads propose from the
            # ADAPTED hidden state — unlike spec mode, the proposer sees
            # the tenant's delta for free
            ids = np.zeros(bb, np.int32)
            for i, s in enumerate(batch):
                ids[i] = s.adapter_id
            adapters = (self.adapters.stacked, jnp.asarray(ids, jnp.int32))
        tables = jnp.asarray(tables, jnp.int32)
        fill = jnp.asarray(fill, jnp.int32)
        last = jnp.asarray(last, jnp.int32)
        prop = jnp.asarray(prop, jnp.int32)

        t0 = journal.now()
        try:
            self._chaos("verify", batch)
            packed, tpools = self._medusa_fn(
                self.pool.pools, self.params, self.medusa_heads, tables, fill,
                last, prop, self._next_rng(), temps, topks, topps, eos, adapters,
                model=self.model, k=k,
            )
        except Exception as exc:  # noqa: BLE001 — the heads are an optimization
            for s in batch:
                # the degraded plain step shifts every row one position, so
                # carried proposals would be stale by one — drop them
                s.medusa_pending = None
            self._degrade_round(batch, t0, bb, exc, label="medusa_degrade")
            return
        self.pool.swap(tpools)
        # ONE fetch: tokens and the n_new/n_accept counters ride together
        out = np.asarray(packed)
        now = time.perf_counter()
        journal.emit("medusa", t0, label=f"b{bb}", active=len(batch),
                     bucket=bb, blocks=nb, k=k, traces=[s.trace for s in batch])
        self.ledger.step_sample(self.scheduler.depth(), len(batch))
        if self.metrics is not None:
            self._m_batch.set(len(batch))
        for i, s in enumerate(batch):
            n_new = int(out[i, k])
            if k > 1:
                self.ledger.spec_round(
                    s.req.id, drafted=k - 1, accepted=int(out[i, k + 1])
                )
                if self.metrics is not None:
                    self._m_drafted.inc(k - 1)
                    self._m_accepted.inc(int(out[i, k + 1]))
                s.medusa_pending = out[i, k + 2 : 2 * k + 1].copy()
            for tok in out[i, :n_new]:
                prev_last = s.last_token
                s.fill += 1  # this token's K/V was written by the round
                self._emit(s, int(tok), now)
                if s.finished is not None:
                    break
                s.prev_token = prev_last

    def _degrade_round(self, batch, t0: float, bb: int, exc: BaseException,
                       label: str = "draft_degrade") -> None:
        """A failed PROPOSE step (the spec draft or the fused Medusa
        round) degrades the round to plain decode: proposals are an
        optimization, so losing them costs throughput (no ``spec_round``
        events this round — accept counters stay exact), never
        correctness or identity. The draft cache misses the degraded
        token's slot; the next healthy round's 2-token leading rewrite
        closes one slot and any unwritten remainder only costs accept
        rate (the same posture as prefix-skipped draft prefill). Medusa
        has no second cache, so its degraded round loses nothing at all.
        A failure inside the fallback decode propagates to ``step``'s
        handler, which fails the batch."""
        journal.emit("fault", t0, label=f"b{bb}:{label}", active=bb,
                     error=f"{type(exc).__name__}: {exc}",
                     traces=[s.trace for s in batch])
        self._decode(batch)

    def _emit(self, seq, tok: int, now: float) -> None:
        seq.out.append(tok)
        self.ledger.token(seq.req.id)
        if self.metrics is not None:
            self._m_tokens.inc()
            t_prev = getattr(seq, "_last_tok_t", None)
            if t_prev is not None:
                self._m_itl.observe(now - t_prev)
            seq._last_tok_t = now
        if tok == seq.eos_id or len(seq.out) >= seq.req.max_new_tokens:
            if self.prefix is not None and seq.fill > seq.prompt_len:
                # multi-turn sharing: publish the full blocks the decode
                # extended (K/V written through position fill-1; a spec
                # round's stale tail lives past fill, in blocks this
                # slice never reaches). finish() then drops only this
                # request's references — adopted pages stay cached.
                written = np.concatenate(
                    [np.asarray(seq.req.prompt, np.int32),
                     np.asarray(seq.out, np.int32)]
                )[: seq.fill]
                self.prefix.insert(written, seq.blocks, adapter=seq.adapter_id)
            self.scheduler.finish(seq, now)
            self._record_terminal(seq, now)
        else:
            seq.last_token = tok
