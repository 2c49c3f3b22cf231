"""``dmlcloud_tpu.lint`` — AST-based TPU-hazard linter.

PR 1's overlap engine removed every host-sync point from the hot loop;
this package keeps it that way. A
pure-stdlib AST pass detects, at review time and on CPU, the hazard
patterns the framework exists to avoid — the things that silently claw the
win back when the next ``Stage`` subclass reintroduces them:

==========  ============================================================
DML101      host sync inside step/epoch code (``.item()``, ``float()``/
            ``np.asarray()`` on traced values, ``jax.device_get``,
            ``print`` of arrays) — defeats ``deferred_metrics()``
DML102      Python/NumPy RNG inside a jitted step fn — baked in at trace
            time, breaks reproducibility and randomness at once
DML103      ``jax.jit``/``pjit`` train step without donated train state —
            params + optimizer state held twice in HBM
DML104      retrace hazards: data-dependent ``if``/``while``/iteration on
            traced values (runtime companion: :class:`TraceGuard`)
DML105      blocking ``checkpoint.save``/``wandb`` calls inside the epoch
            loop — serialization/network on the training thread
DML106      wall-clock timing of dispatches without ``block_until_ready``
            — benchmarks that measure enqueue cost, not execution
DML107      ``jax.jit``/``pjit`` call inside a loop body — re-traces and
            re-compiles every iteration
DML108      ``time.time()`` for step timing — NTP steps corrupt durations
DML201      collective ``axis_name`` that no mesh declares (resolved
            through assignments and across files — flow-aware)
DML202      ``shard_map`` spec arity / unknown ``PartitionSpec`` axis
DML203      collective in host-side code outside any trace context
DML204      value read again after ``donate_argnums`` donated its buffers
DML205      jitted train/decode step returns an updated state/KV-cache
            argument without donating it — the buffer is held twice
            (flow-aware: read-only consumers stay silent)
DML206      ``lax.scan``/``nn.scan`` over a layer stack without a remat
            policy — activation memory grows with depth
DML301      shared attribute locked on one side of a thread boundary only
DML302      ``time.sleep`` polling loop where an Event/Condition exists
DML6xx      the IR pass (``lint --ir`` / ``python -m dmlcloud_tpu
            verify``): rules over the TRACED program — jaxpr + compiled
            artifact — not the source. DML601 donation declared but
            silently dropped by jit (the compiled executable aliases
            nothing); DML602 collective/sharding axes that don't resolve
            against the actual mesh; DML603 host callbacks baked into a
            step program; DML604 estimated peak memory over a declared
            HBM budget; DML605 enumerated signature surface over the
            TraceGuard budget. Checks live in rules_ir.py (stdlib); the
            tracer in lint/ir.py is the ONE jax-importing lint module
            and is loaded lazily.
DML501      ``KVBlockPool.alloc``/``PrefixCache.lock`` reference leaked on
            some path out of the owning scope (whole-program, path- and
            helper-aware — subsumes the DML212 identifier heuristic)
DML502      paged ``scatter_tokens`` write reachable without a preceding
            COW guard/fork, across modules and import renames (upgrades
            DML211 from vocabulary scoping to resolved references)
DML503      terminate/finalize-family path exiting with zero or 2+
            ``TERMINAL_STATUSES`` stamps — the single-exit contract
DML504      DML301's lockset check across module boundaries: thread-target
            closures through helpers and inherited methods
==========  ============================================================

DML5xx run in the whole-program pass of ``lint_paths`` (lint/callgraph.py
summaries + lint/lifecycle.py rules; ``--no-callgraph`` disables). The
incremental cache (lint/cache.py, ``--cache``) re-lints only changed
files and their reverse importers; ``--fix`` applies the mechanical
repairs in lint/fix.py.

Entry points: ``lint_source``/``lint_file``/``lint_paths`` (library),
``python -m dmlcloud_tpu lint`` (CLI; ``--format=github``, ``--jobs N``),
``TrainingPipeline(lint="warn")`` (lints registered Stage subclasses at
run start), and ``TrainingPipeline(sanitize="warn"|"error")`` — the
runtime sanitizer arm (lint/sanitize.py): implicit-transfer probes +
``jax_debug_nans`` reporting through the same Finding schema and the
telemetry journal. Suppress a finding with ``# dmllint: disable=DML101 --
justification`` (family wildcards like ``DML2xx`` work). Full catalog
with bad/good examples: doc/lint.md.
"""

from .engine import (  # noqa: F401
    Finding,
    IR_RULES,
    LintError,
    PROJECT_RULES,
    RULES,
    build_project_context,
    lint_file,
    lint_paths,
    lint_source,
)
from . import rules  # noqa: F401  — importing registers the rules
from . import rules_sharding  # noqa: F401  — DML2xx sharding/collective family
from . import rules_perf  # noqa: F401  — DML205/206 donation & remat contracts
from . import rules_data  # noqa: F401  — DML209 packed segment_ids contract
from . import rules_concurrency  # noqa: F401  — DML3xx concurrency family
from . import lifecycle  # noqa: F401  — DML5xx whole-program lifecycle family
from . import rules_ir  # noqa: F401  — DML6xx IR family (checks only; the jax tracer is lint/ir.py, loaded lazily)
from .cache import DEFAULT_CACHE_PATH, LintCache  # noqa: F401
from .callgraph import ProjectGraph, summarize_module  # noqa: F401
from .fix import FIXABLE_RULES, apply_fixes, apply_suppressions  # noqa: F401
from .sanitize import SANITIZE_MODES, Sanitizer, SanitizerError  # noqa: F401
from .traceguard import RetraceError, TraceGuard  # noqa: F401

__all__ = [
    "DEFAULT_CACHE_PATH",
    "FIXABLE_RULES",
    "Finding",
    "IR_RULES",
    "LintCache",
    "LintError",
    "PROJECT_RULES",
    "ProjectGraph",
    "RULES",
    "RetraceError",
    "SANITIZE_MODES",
    "Sanitizer",
    "SanitizerError",
    "TraceGuard",
    "apply_fixes",
    "apply_suppressions",
    "build_project_context",
    "lint_file",
    "lint_paths",
    "lint_source",
    "summarize_module",
]
