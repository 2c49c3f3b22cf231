"""The donation/remat/allocation performance-contract rules (DML205-DML208).

PR 6's kernel pass made the hot paths fast; these rules make the memory
contracts that keep them fast checkable on CPU:

- DML205  a jitted train/decode step that RETURNS an updated version of a
          TrainState / optimizer-state / KV-cache argument without
          donating it — the old buffer stays live across the call, so the
          biggest tensors in the program are held twice
- DML206  ``lax.scan``/``nn.scan`` over a layer stack without a remat
          policy — every layer's activations are saved for the backward,
          so activation memory grows with depth instead of staying O(1)
- DML208  ``init_cache(...)`` / ``KVBlockPool(...)`` — a full KV-cache
          allocation — inside a ``for``/``while`` body: a serve/request
          loop that reallocates the cache per request churns the biggest
          allocation in the program every iteration instead of reusing a
          pool (serve/kv_pool.py) or rewinding (generate.rewind_cache)
- DML210  host readback of an on-device accept/round COUNTER inside a
          serve/decode loop (``.item()``/``int()``/``np.asarray()`` on
          accept counts per round) — one extra device sync per round;
          counters must stay on
          device or ride the loop's one token fetch (packed columns,
          serve/engine.py's pattern)
- DML211  a paged-scatter call (or a block-table-entry write) with NO
          preceding copy-on-write fork / refcount check, in code that
          handles SHARED blocks (prefix sharing, serve/prefix_cache.py):
          a block with refcount > 1 is mapped read-only into other
          requests' tables — writing through it silently corrupts every
          other reader's cached prefix, a cross-request correctness bug
          no test on the writing request can see
- DML212  in serving-lifecycle code, a ``try/except`` around a serve
          step call (or a request's transition to a terminal status)
          whose handler neither frees pool blocks nor routes the request
          through the lifecycle's exit path — the leak-on-error hazard:
          the swallowed failure strands the request live and its pages
          (COW spare, prefix locks) stay allocated forever
- DML213  in router-loop code (the multi-replica front door —
          heartbeats, failover, circuit breakers), an UNBOUNDED blocking
          receive: ``queue.get()`` / ``Connection.recv()`` /
          ``Event.wait()`` with no ``timeout=`` — one wedged replica (or
          an empty queue) parks the loop forever, so heartbeat deadlines
          are never checked and every replica behind the router looks
          dead at once
- DML215  unbounded metric label cardinality: a ``.labels(...)`` call in
          a per-request/per-step loop whose label value resolves to a
          request id / idempotency token / trace id (one SERIES minted
          per request — memory grows with traffic forever), or a
          registry ``counter()``/``gauge()``/``histogram()`` create in a
          loop with a per-request dynamic NAME (one FAMILY per request).
          Flow-aware: a bare name is chased to its binding. Resolve the
          series handle once outside the loop and key labels by a
          bounded vocabulary (status, replica, tenant tier) — the
          registry's ``max_series`` overflow valve is a backstop, not a
          design (telemetry/metrics_registry.py)

Both are flow-aware (built on lint/dataflow.py): DML205 only fires when
the state argument provably FLOWS TO THE RETURN (a read-only cache in a
scoring function must not be donated — firing there would be a
correctness bug, not a style nit), and the wrapped function is resolved
through decorators, ``jax.jit(fn, ...)`` calls and ``functools.partial``
forms. DML103 keeps its syntactic "train step with no donation at all"
ground; DML205 covers what it cannot: donation present but MISSING an
argument, and decode steps (cache-carrying functions DML103's name
heuristic never sees). Sites DML103 already reports are skipped so one
mistake yields one finding.
"""

from __future__ import annotations

import ast
import re

from . import dataflow
from .engine import (
    Finding,
    ModuleCtx,
    _compute_taint,
    _donated_argnums,
    _static_params,
    attr_chain,
    rule,
)
from .rules import _is_trainish

__all__ = [
    "check_step_donation",
    "check_scan_remat",
    "check_cache_alloc_in_loop",
    "check_counter_readback_in_loop",
    "check_unguarded_shared_block_write",
    "check_leaky_failure_handler",
    "check_unbounded_blocking_receive",
    "check_metric_label_cardinality",
]


def _f(ctx: ModuleCtx, rule_id: str, node: ast.AST, message: str, context: str = "") -> Finding:
    return Finding(rule_id, ctx.path, node.lineno, node.col_offset, message, context)


def _stateful_param(name: str) -> bool:
    """Parameter names that carry the double-buffer hazard: train/optimizer
    state and KV caches. ``params`` is deliberately NOT here — donating the
    params of an eval/decode function that merely reads them would be a
    correctness bug, and train-state donation is DML103's ground."""
    n = name.lower()
    return n in ("state", "opt", "optimizer", "kv") or n.endswith("state") or n.endswith("cache")


def _param_names(fn) -> list[str]:
    args = fn.args
    return [a.arg for a in args.posonlyargs + args.args]


def _own_returns(fn):
    """Return statements of ``fn``'s own scope (nested defs excluded)."""
    for node in dataflow._body_walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            yield node


#: receiver methods whose result IS a new version of the receiver
_UPDATEISH = frozenset({"apply_gradients", "replace", "update", "updated", "set"})

#: a returned binding named like state/cache counts as the updated buffer
_STATEFUL_STEM = re.compile(r"(?i)(state|cache|opt\b|opt_|_opt|kv)")


def _returns_updated(fn, pname: str, tainted: set[str]) -> bool:
    """Whether ``fn`` returns something that IS a new version of parameter
    ``pname`` — the param itself, an update-method call on it
    (``state.apply_gradients(...)``), arithmetic on the bare param
    (``state - grads``), or a tainted binding named like the state kind
    (``new_cache``). Values merely DERIVED from the state (a loss, logits)
    do not count: donating their source would be a correctness bug."""

    def element_hits(e: ast.AST) -> bool:
        if isinstance(e, ast.Name):
            return e.id == pname or (e.id in tainted and bool(_STATEFUL_STEM.search(e.id)))
        if isinstance(e, ast.Call) and isinstance(e.func, ast.Attribute):
            chain = attr_chain(e.func)
            if chain and chain[0] == pname and e.func.attr in _UPDATEISH:
                return True
        if isinstance(e, ast.BinOp):
            return any(
                isinstance(side, ast.Name) and side.id == pname for side in (e.left, e.right)
            )
        return False

    for r in _own_returns(fn):
        elts = r.value.elts if isinstance(r.value, ast.Tuple) else [r.value]
        if any(element_hits(e) for e in elts):
            return True
    return False


def _donated_argnames(jit_kwargs: dict) -> set[str]:
    names: set[str] = set()
    kw = jit_kwargs.get("donate_argnames")
    if kw is not None:
        for c in ast.walk(kw):
            if isinstance(c, ast.Constant) and isinstance(c.value, str):
                names.add(c.value)
    return names


# ------------------------------------------------------------------- DML205


@rule("DML205", "jitted step does not donate its state/cache argument")
def check_step_donation(ctx: ModuleCtx):
    """A jitted step that consumes a TrainState/optimizer-state/KV-cache
    argument and returns an updated version of it, without donating the
    argument, keeps BOTH versions live across the call — for a train step
    that is params+optimizer state twice, for a decode step the whole KV
    cache twice. Flow-aware: fires only when the stateful argument
    provably reaches a return value (read-only consumers stay silent —
    donating those would be a bug), and only for arguments the site's
    ``donate_argnums``/``donate_argnames`` misses."""
    defs_by_name: dict[str, list[ast.AST]] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(node.name, []).append(node)

    seen: set[tuple[int, int, str]] = set()
    for site in ctx.jit_sites:
        if site.target_name is None:
            continue
        if _is_trainish(site.target_name) and not (
            "donate_argnums" in site.kwargs or "donate_argnames" in site.kwargs
        ):
            continue  # DML103's finding; one mistake, one report
        defs = defs_by_name.get(site.target_name, [])
        if len(defs) != 1:
            continue  # ambiguous or unresolvable: silence, never a guess
        fn = defs[0]
        params = _param_names(fn)
        statics = _static_params(fn, site.kwargs)
        donated_idx = _donated_argnums(site.kwargs)
        donated_names = _donated_argnames(site.kwargs)
        for idx, pname in enumerate(params):
            if pname in ("self", "cls") or not _stateful_param(pname):
                continue
            if pname in statics or idx in donated_idx or pname in donated_names:
                continue
            # flow check: is a NEW version of the state actually returned?
            tainted = _compute_taint(fn, {pname})
            if not _returns_updated(fn, pname, tainted):
                continue  # read-only consumer: donation would be WRONG
            key = (site.lineno, site.col, pname)
            if key in seen:
                continue
            seen.add(key)
            yield _f(
                ctx, "DML205", site.node,
                f"jitted step '{site.target_name}' returns an updated '{pname}' "
                f"but does not donate it (add {idx} to donate_argnums): the old "
                "buffer stays live across the call, holding the "
                + ("KV cache" if pname.lower().endswith("cache") or pname.lower() == "kv"
                   else "train/optimizer state")
                + " twice",
                site.target_name,
            )


# ------------------------------------------------------------------- DML206

#: callee name (terminal segment) that identifies a transformer layer/block
_LAYERISH = re.compile(r"(?i)(block|layer)s?(_?\d+)?$")
_REMAT_NAMES = ("checkpoint", "remat")


def _is_remat_call(ctx: ModuleCtx, node: ast.AST) -> bool:
    """``jax.checkpoint(f)`` / ``jax.remat(f)`` / ``nn.remat(Block)`` /
    ``functools.partial(jax.checkpoint, ...)`` call expressions."""
    if not isinstance(node, ast.Call):
        return False
    resolved = ctx.resolve(node.func) or ""
    last = resolved.split(".")[-1] if resolved else ""
    if not last and isinstance(node.func, ast.Attribute):
        last = node.func.attr
    if last in _REMAT_NAMES:
        return True
    if last == "partial" and node.args:
        return _is_remat_call(ctx, ast.Call(func=node.args[0], args=[], keywords=[])) or (
            (ctx.resolve(node.args[0]) or "").split(".")[-1] in _REMAT_NAMES
        )
    return False


def _has_remat_decorator(ctx: ModuleCtx, fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        resolved = ctx.resolve(dec) or ""
        if resolved.split(".")[-1] in _REMAT_NAMES:
            return True
        if isinstance(dec, ast.Call) and _is_remat_call(ctx, dec):
            return True
    return False


def _bare_layer_call(ctx: ModuleCtx, body: ast.AST, scopes) -> ast.Call | None:
    """First call inside ``body`` whose callee names a layer/block and is
    not (provably) remat-wrapped — the hazard DML206 reports."""
    for node in ast.walk(body):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        seg = None
        if isinstance(func, ast.Attribute):
            seg = func.attr
        elif isinstance(func, ast.Name):
            seg = func.id
            # a name bound to nn.remat(Block)/jax.checkpoint(f) is wrapped
            bound = dataflow.resolve_expr(func, scopes)
            if _is_remat_call(ctx, bound):
                continue
        if seg and _LAYERISH.search(seg):
            return node
    return None


# ------------------------------------------------------------------- DML208

#: callables whose result is a FULL KV cache / cache pool — the biggest
#: single allocation an inference program makes
_CACHE_ALLOC_NAMES = frozenset({"init_cache", "KVBlockPool"})


def _cache_alloc_name(ctx: ModuleCtx, node: ast.Call, scopes) -> str | None:
    """The cache-allocator name a call resolves to, chasing import aliases
    (``gen.init_cache``) and local assignment aliases (``alloc =
    init_cache; alloc(...)``) through the dataflow core. None when the
    callee is provably something else or unresolvable."""
    func = node.func
    resolved = ctx.resolve(func) or ""
    last = resolved.split(".")[-1] if resolved else ""
    if not last and isinstance(func, ast.Attribute):
        last = func.attr
    if last in _CACHE_ALLOC_NAMES:
        return last
    if isinstance(func, ast.Name):
        bound = dataflow.resolve_expr(func, scopes)
        if bound is not None and bound is not func:
            chained = (ctx.resolve(bound) or "").split(".")[-1]
            if not chained and isinstance(bound, ast.Name):
                chained = bound.id
            if chained in _CACHE_ALLOC_NAMES:
                return chained
    return None


@rule("DML208", "full KV-cache allocation inside a request/serve loop")
def check_cache_alloc_in_loop(ctx: ModuleCtx):
    """``init_cache(...)`` builds the full ``[B, S, KH, D]``-per-layer
    cache tree; ``KVBlockPool(...)`` builds the whole page pool. Either
    one inside a ``for``/``while`` body — the shape of a request/serve
    loop — reallocates (and re-zeroes, and re-uploads) the single biggest
    buffer in an inference program once per iteration: allocation churn
    that fragments HBM and stalls the loop on every request. Allocate
    ONCE before the loop and reuse it — a pool recycles blocks per
    request (serve/kv_pool.py), a dense cache rewinds
    (``generate.rewind_cache``). Flow-aware: callee names are chased
    through import and assignment aliases; functions *defined* inside the
    loop run at call time, not per iteration, and are skipped (same
    exemption as DML107)."""

    def visit(node: ast.AST, in_loop: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                # the nested body executes when called, not per iteration
                yield from visit(child, False)
                continue
            if in_loop and isinstance(child, ast.Call):
                name = _cache_alloc_name(ctx, child, ctx.scopes_at(child))
                if name is not None:
                    fn = ctx.enclosing_function(child)
                    yield _f(
                        ctx, "DML208", child,
                        f"{name}(...) inside a loop body reallocates the full KV "
                        "cache every iteration (allocation churn on the biggest "
                        "buffer in the program); allocate once before the "
                        "request/serve loop and reuse it — recycle pool blocks "
                        "(serve.KVBlockPool) or rewind the dense cache "
                        "(generate.rewind_cache)",
                        getattr(fn, "name", ""),
                    )
            yield from visit(
                child, in_loop or isinstance(child, (ast.For, ast.AsyncFor, ast.While))
            )

    yield from visit(ctx.tree, False)


# ------------------------------------------------------------------- DML210

#: names that identify a speculative-decode / verification counter — the
#: values a draft/verify round produces ON DEVICE (accept counts, round
#: counters). Deliberately narrow: token fetches (the loop's one sanctioned
#: sync) and generic values never match.
_COUNTER_STEM = re.compile(r"(?i)(accept|n_acc|draft_count|drafted|n_rounds|rounds|num_rounds)")

#: host-materialisation spellings DML210 watches inside loop bodies
_READBACK_FNS = frozenset({"int", "float"})
_READBACK_RESOLVED = frozenset({"numpy.asarray", "numpy.array", "jax.device_get"})


def _counterish(expr: ast.AST) -> bool:
    """Whether an expression names a counter: an identifier, attribute or
    string key matching the counter vocabulary anywhere inside it."""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Name) and _COUNTER_STEM.search(sub.id):
            return True
        if isinstance(sub, ast.Attribute) and _COUNTER_STEM.search(sub.attr):
            return True
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str) and _COUNTER_STEM.search(sub.value):
            return True
    return False


def _counter_arg(arg: ast.AST, scopes) -> bool:
    """``arg`` (the readback call's operand) names a counter — directly,
    or after chasing a bare name to its binding through the dataflow core
    (``acc = stats["accepted"]; int(acc)`` is the flow-aware case)."""
    if _counterish(arg):
        return True
    if isinstance(arg, ast.Name):
        bound = dataflow.resolve_expr(arg, scopes)
        if bound is not None and bound is not arg:
            return _counterish(bound)
    return False


@rule("DML210", "per-round host readback of an on-device counter in a serve/decode loop")
def check_counter_readback_in_loop(ctx: ModuleCtx):
    """A serve/decode loop that reads its accept/round counters back to
    host EVERY iteration — ``counter.item()``, ``int(counter)``,
    ``float(counter)``, ``np.asarray(counter)``, ``jax.device_get(counter)``
    inside a ``for``/``while`` body — pays one extra device sync per
    round on top of the loop's one sanctioned token fetch: per-round
    counter readbacks serialize every round against the dispatch queue.
    Keep the counters on device across rounds, or pack
    them into the same array the loop already fetches (the serving
    engine returns ``[tokens | n_new | n_accept]`` as ONE fetch —
    serve/engine.py). Flow-aware: a bare name is chased to its binding
    (``acc = stats["accepted"]; int(acc)`` still fires); a readback
    AFTER the loop — once per trace, not per round — never matches, and
    functions *defined* inside the loop run at call time and are skipped
    (DML107/DML208's exemption)."""

    def hit(call: ast.Call):
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "item" and not call.args:
            return _counter_arg(func.value, ctx.scopes_at(call))
        arg = call.args[0] if call.args else None
        if arg is None:
            return False
        if isinstance(func, ast.Name) and func.id in _READBACK_FNS and func.id not in ctx.aliases:
            return _counter_arg(arg, ctx.scopes_at(call))
        resolved = ctx.resolve(func) or ""
        if resolved in _READBACK_RESOLVED:
            return _counter_arg(arg, ctx.scopes_at(call))
        return False

    def visit(node: ast.AST, in_loop: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                # the nested body executes when called, not per iteration
                yield from visit(child, False)
                continue
            if in_loop and isinstance(child, ast.Call) and hit(child):
                fn = ctx.enclosing_function(child)
                yield _f(
                    ctx, "DML210", child,
                    "host readback of an on-device counter inside a serve/decode "
                    "loop: one extra device sync per round (the r05 0.19x "
                    "speculative regression); keep accept/round counters on "
                    "device, or pack them into the loop's single token fetch "
                    "(serve/engine.py returns [tokens | n_new | n_accept] as "
                    "one array)",
                    getattr(fn, "name", ""),
                )
            yield from visit(
                child, in_loop or isinstance(child, (ast.For, ast.AsyncFor, ast.While))
            )

    yield from visit(ctx.tree, False)


# ------------------------------------------------------------------- DML211

#: identifiers that mark a module as HANDLING SHARED BLOCKS — prefix-cache
#: machinery (the radix tree, refcounts, copy-on-write). Only such modules
#: are in scope: traced kernel code (ops/, models/) cannot see host-side
#: refcounts and legitimately scatters unconditionally.
_SHARING_VOCAB = re.compile(
    r"(?i)(prefix_?cache|radix|shared_blocks?|refcount|(^|_)cow(_|$)|copy_on_write)"
)

#: a call whose terminal name matches this counts as the COW fork /
#: refcount check that must precede a shared-block write
_COW_GUARD = re.compile(r"(?i)(cow|refcount|is_shared|writable|fork)")

#: block-table receivers: a subscript STORE into one of these is a
#: table-entry write (remapping which physical page a row reads/writes)
_TABLEISH = re.compile(r"(?i)(block_)?tables?$")


def _module_handles_shared_blocks(ctx: ModuleCtx) -> bool:
    """Whether the module's IDENTIFIERS (names, attributes, imports,
    parameters, keywords — never docstrings or comments) mention the
    prefix-sharing machinery."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Name) and _SHARING_VOCAB.search(node.id):
            return True
        if isinstance(node, ast.Attribute) and _SHARING_VOCAB.search(node.attr):
            return True
        if isinstance(node, ast.keyword) and node.arg and _SHARING_VOCAB.search(node.arg):
            return True
        if isinstance(node, ast.arg) and _SHARING_VOCAB.search(node.arg):
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names]
            if isinstance(node, ast.ImportFrom) and node.module:
                names.append(node.module)
            if any(_SHARING_VOCAB.search(n) for n in names):
                return True
    return False


def _is_scatter_call(ctx: ModuleCtx, node: ast.Call) -> bool:
    """``scatter_tokens(...)`` — chased through import aliases
    (``paged.scatter_tokens``) and local assignment aliases (``scat =
    scatter_tokens; scat(...)``) via the dataflow core."""
    func = node.func
    resolved = ctx.resolve(func) or ""
    last = resolved.split(".")[-1] if resolved else ""
    if not last and isinstance(func, ast.Attribute):
        last = func.attr
    if not last and isinstance(func, ast.Name):
        last = func.id
    if last == "scatter_tokens":
        return True
    if isinstance(func, ast.Name):
        bound = dataflow.resolve_expr(func, ctx.scopes_at(node))
        if bound is not None and bound is not func:
            chained = (ctx.resolve(bound) or "").split(".")[-1]
            if not chained and isinstance(bound, ast.Name):
                chained = bound.id
            if chained == "scatter_tokens":
                return True
    return False


def _is_cow_guard_call(node: ast.Call) -> bool:
    func = node.func
    name = func.attr if isinstance(func, ast.Attribute) else (
        func.id if isinstance(func, ast.Name) else ""
    )
    return bool(_COW_GUARD.search(name))


def _table_store_name(stmt: ast.AST) -> str | None:
    """The table-ish receiver of a subscript STORE (``tables[i] = b``,
    ``row.block_tables[i, j] = b``), else None."""
    targets = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    for t in targets:
        if not isinstance(t, ast.Subscript):
            continue
        base = t.value
        name = base.attr if isinstance(base, ast.Attribute) else (
            base.id if isinstance(base, ast.Name) else ""
        )
        if name and _TABLEISH.search(name):
            return name
    return None


@rule("DML211", "paged scatter / block-table write without a preceding COW fork or refcount check")
def check_unguarded_shared_block_write(ctx: ModuleCtx):
    """In code that handles SHARED blocks (the prefix-cache machinery:
    refcounted pools, radix matches, copy-on-write forks), a
    ``scatter_tokens(...)`` call or a block-table-entry write
    (``tables[i] = block``) that no COW fork / refcount check precedes in
    the same function writes through pages other requests may be reading
    — corrupting THEIR cached prefixes, a cross-request bug the writing
    request's own output never shows. The guard must come FIRST (a fork
    swaps the table entry, so tables built before the guard are stale):
    any call naming the contract (``_cow_guard``/``fork``/``refcount``/
    ``is_shared``/``ensure_writable``) earlier in the function body
    sanctions every later write in that function. Flow-aware:
    ``scatter_tokens`` is chased through import and assignment aliases;
    traced kernel modules (no sharing vocabulary) are out of scope — they
    cannot see host refcounts, their callers carry the contract."""
    if not _module_handles_shared_blocks(ctx):
        return

    hazards: list[tuple[ast.AST, str, ast.AST | None]] = []
    guards: dict[ast.AST | None, int] = {}  # enclosing fn -> first guard line
    for node in ast.walk(ctx.tree):
        fn = ctx.enclosing_function(node)
        if isinstance(node, ast.Call):
            if _is_cow_guard_call(node):
                guards[fn] = min(guards.get(fn, node.lineno), node.lineno)
            elif _is_scatter_call(ctx, node):
                hazards.append((node, "scatter_tokens(...) paged write", fn))
        else:
            name = _table_store_name(node)
            if name is not None:
                hazards.append((node, f"write to block table entry '{name}[...]'", fn))

    for node, what, fn in hazards:
        first_guard = guards.get(fn)
        if first_guard is not None and first_guard < node.lineno:
            continue  # fork/refcount check precedes: the contract is held
        yield _f(
            ctx, "DML211", node,
            f"{what} with no preceding COW fork / refcount check in "
            "shared-block code: a refcount>1 block is mapped read-only into "
            "other requests' tables — fork it first (ServeEngine._cow_guard: "
            "copy the page, swap the table entry, release the shared "
            "original), then build the tables the scatter uses",
            getattr(fn, "name", ""),
        )


@rule("DML206", "scan over a layer stack without a remat policy")
def check_scan_remat(ctx: ModuleCtx):
    """``lax.scan`` over a stack of transformer layers saves EVERY layer's
    activations for the backward pass — the per-layer memory times depth,
    exactly what rematerialisation exists to cap. Fires when a scan body
    (resolved through assignments, lambdas and local defs) calls something
    layer/block-named with no ``jax.checkpoint``/``jax.remat``/``nn.remat``
    anywhere on the path. Non-layer scans (decode steps, loss chunking,
    ring hops) never match; an already-checkpointed body, a remat
    decorator, or a ``nn.remat``-wrapped class all count as the policy
    being present."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func) or ""
        if resolved not in ("jax.lax.scan", "flax.linen.scan") and not (
            resolved.endswith(".scan") and resolved.startswith(("jax.lax", "flax.linen"))
        ):
            continue
        if not node.args:
            continue
        body_arg = node.args[0]
        if _is_remat_call(ctx, body_arg):
            continue  # scan(jax.checkpoint(body), ...)
        scopes = ctx.scopes_at(node)
        resolved_body = dataflow.resolve_expr(body_arg, scopes)
        fn_name = getattr(ctx.enclosing_function(node), "name", "")

        body = None
        if isinstance(resolved_body, ast.Lambda):
            body = resolved_body
        elif isinstance(resolved_body, ast.Call) and _is_remat_call(ctx, resolved_body):
            continue  # body = jax.checkpoint(f); scan(body, ...)
        elif isinstance(resolved_body, ast.Name):
            defs = [
                d for d in ast.walk(ctx.tree)
                if isinstance(d, (ast.FunctionDef, ast.AsyncFunctionDef))
                and d.name == resolved_body.id
            ]
            if len(defs) == 1:
                body = defs[0]
                if _has_remat_decorator(ctx, body):
                    continue
            elif _LAYERISH.search(resolved_body.id):
                # nn.scan(DecoderBlock, ...): the scanned TARGET is the layer
                yield _f(
                    ctx, "DML206", node,
                    f"scan over layer class '{resolved_body.id}' without a remat "
                    "policy: every layer's activations are saved for the backward "
                    "— wrap it in nn.remat (or jax.checkpoint the body) so "
                    "activation memory stays O(1) layers",
                    fn_name,
                )
                continue
        if body is None:
            continue
        hit = _bare_layer_call(ctx, body, scopes)
        if hit is not None:
            yield _f(
                ctx, "DML206", node,
                "scan over a layer stack without a remat policy: every layer's "
                "activations are saved for the backward — wrap the scan body in "
                "jax.checkpoint (jax.remat) so activation memory stays O(1) layers",
                fn_name,
            )


# ------------------------------------------------------------------- DML212

#: identifiers that mark a module as SERVING-LIFECYCLE code — the engine,
#: its block pools, chunked prefill / bucketed decode. Only such modules
#: are in scope: a TRAINING loop's try around its step function has its
#: own recovery contract (checkpoint + requeue verdict), not a block pool
#: holding pages on behalf of the failed work.
_SERVE_LIFECYCLE_VOCAB = re.compile(
    r"(?i)(serve_?engine|serve_?ledger|kv_?block_?pool|pool_?exhausted"
    r"|prefill_?chunk|chunked_?prefill|decode_?batch|prefix_?cache"
    r"|continuous_?batching|paged_?kv|block_?tables?)"
)

#: a call whose terminal name is the serving step family — the calls whose
#: failure strands requests mid-flight, pages still allocated
_STEPLIKE_CALL = re.compile(
    r"(?i)(^|_)(step|prefill|decode|draft|verify)"
    r"(_fn|_chunk|_batch|_step|_spec|_round|_tokens)?$"
)

#: handler calls that COUNT as routing the failure into the request
#: lifecycle: releasing pages, stamping a terminal status through the one
#: exit path, shedding, or degrading the round
_LIFECYCLE_SANCTION = re.compile(
    r"(?i)(release|free|terminate|fail|abort|shed|finish|cancel|drop|unlock|degrade)"
)

#: the request state machine's terminal statuses (serve/scheduler.py) —
#: an assignment of one of these inside a try body is a state transition
#: whose failure handler must not swallow the exception without cleanup
_TERMINAL_STATUS_VALUES = frozenset(
    {"ok", "cancelled", "deadline_exceeded", "shed", "error"}
)


def _module_is_serving_lifecycle(ctx: ModuleCtx) -> bool:
    """Whether the module's IDENTIFIERS (names, attributes, imports,
    parameters, keywords — never docstrings or comments) mention the
    serving-lifecycle machinery."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Name) and _SERVE_LIFECYCLE_VOCAB.search(node.id):
            return True
        if isinstance(node, ast.Attribute) and _SERVE_LIFECYCLE_VOCAB.search(node.attr):
            return True
        if isinstance(node, ast.keyword) and node.arg and _SERVE_LIFECYCLE_VOCAB.search(node.arg):
            return True
        if isinstance(node, ast.arg) and _SERVE_LIFECYCLE_VOCAB.search(node.arg):
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names]
            if isinstance(node, ast.ImportFrom) and node.module:
                names.append(node.module)
            if any(_SERVE_LIFECYCLE_VOCAB.search(n) for n in names):
                return True
    return False


def _try_own_body(node: ast.Try):
    """Every node of ``node.body``'s own scope: nested ``try`` blocks own
    their handling (they are examined on their own) and nested ``def``/
    ``lambda`` bodies run later, outside these handlers — both excluded.
    ``orelse``/``finally`` are excluded too: exceptions raised there are
    NOT caught by this try's handlers."""
    stack = list(node.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.Try, ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _step_hazard(node: ast.Try) -> str | None:
    """What makes this try a lifecycle hazard: the first step-family call
    or terminal-status store in its (own-scope) body, else None."""
    for n in _try_own_body(node):
        if isinstance(n, ast.Call):
            func = n.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name and _STEPLIKE_CALL.search(name):
                return f"step call '{name}(...)'"
        elif isinstance(n, ast.Assign) and isinstance(n.value, ast.Constant):
            for t in n.targets:
                if (
                    isinstance(t, ast.Attribute)
                    and t.attr == "status"
                    and n.value.value in _TERMINAL_STATUS_VALUES
                ):
                    return f"terminal-status transition 'status = {n.value.value!r}'"
    return None


def _handler_routes_failure(handler: ast.excepthandler) -> bool:
    """Whether the except handler routes the failure into the lifecycle:
    any ``raise`` (escalation — the caller's handler owns the cleanup) or
    a call naming the contract (release/free/terminate/fail/shed/finish/
    cancel/degrade — the one-exit-path family that frees pool blocks, COW
    spares and prefix locks)."""
    for n in ast.walk(handler):
        if isinstance(n, ast.Raise):
            return True
        if isinstance(n, ast.Call):
            func = n.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name and _LIFECYCLE_SANCTION.search(name):
                return True
    return False


@rule("DML212", "serve step failure handler that neither frees blocks nor stamps a terminal status")
def check_leaky_failure_handler(ctx: ModuleCtx):
    """In serving-lifecycle code (the engine, its pools, chunked prefill /
    bucketed decode), a ``try/except`` around a step-family call — or
    around a request's transition to a terminal status — whose handler
    neither releases pool pages nor routes the request through the
    lifecycle's exit path is the leak-on-error hazard: the exception is
    swallowed, the request never reaches a terminal status, and its
    blocks (plus any COW spare and prefix locks) stay allocated forever —
    the pool bleeds capacity on exactly the nights failures cluster. The
    handler must either escalate (``raise``) or name the contract: a
    release/free call, or the one exit path that stamps the terminal
    status and frees everything (``Scheduler.terminate`` /
    ``ServeEngine._fail`` / ``_degrade_round``). Training modules are out
    of scope — their step failures are the checkpoint/requeue contract's
    ground, not a block pool's."""
    if not _module_is_serving_lifecycle(ctx):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Try):
            continue
        hazard = _step_hazard(node)
        if hazard is None:
            continue
        fn_name = getattr(ctx.enclosing_function(node), "name", "")
        for handler in node.handlers:
            if _handler_routes_failure(handler):
                continue
            yield _f(
                ctx, "DML212", handler,
                f"failure handler around {hazard} neither frees blocks nor "
                "stamps a terminal status: the request is stranded live with "
                "its pages (and any COW spare / prefix locks) still allocated "
                "— route it through the one exit path (Scheduler.terminate / "
                "ServeEngine._fail, which releases everything), degrade the "
                "round, or re-raise",
                fn_name,
            )


# ------------------------------------------------------------------- DML213

#: identifiers that mark a module as ROUTER-LOOP code — the multi-replica
#: front door (serve/router.py): heartbeat health detection, failover,
#: per-replica circuit breakers. Only such modules are in scope: the
#: router's step loop IS the health detector, so any unbounded block
#: inside it silently disables failure detection for every replica at
#: once. Deliberately NOT keyed on bare "replica" — that is sharding
#: vocabulary all over the training stack (replica groups, per-replica
#: batch), where a worker thread's blocking get has no heartbeat contract
#: to violate.
_ROUTER_LOOP_VOCAB = re.compile(
    r"(?i)(router|heart_?beat|fail_?over|circuit_?breaker|front_?door"
    r"|replica_?(kill|stall|drain))"
)

#: constructor terminal names that TYPE a receiver when its binding is
#: chased through the dataflow core: ``inbox = queue.Queue()`` types
#: ``inbox`` queue-like no matter what it is called
_QUEUE_CTOR = re.compile(r"(?i)^(simple|lifo|priority|joinable)?queue$")
_EVENT_CTOR = re.compile(r"(?i)^(event|condition)$")
_CONN_CTOR = re.compile(r"(?i)^pipe$")

#: receiver-identifier fallback for receivers the dataflow core cannot
#: chase (attributes, parameters): names that read as a queue / event /
#: pipe endpoint
_QUEUEISH_NAME = re.compile(r"(?i)((^|_)q(ueue)?s?$|inbox|mailbox|chan(nel)?$|work_?items?$)")
_EVENTISH_NAME = re.compile(
    r"(?i)((^|_)ev(ent)?$|(^|_)cond(ition)?$|ready$|done$|stop(ped)?$|shutdown$|quit$)"
)
_CONNISH_NAME = re.compile(r"(?i)(conn(ection)?$|pipe$|sock(et)?$)")


def _module_is_router_loop(ctx: ModuleCtx) -> bool:
    """Whether the module's IDENTIFIERS (names, attributes, imports,
    parameters, keywords — never docstrings or comments) mention the
    router front-door machinery."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Name) and _ROUTER_LOOP_VOCAB.search(node.id):
            return True
        if isinstance(node, ast.Attribute) and _ROUTER_LOOP_VOCAB.search(node.attr):
            return True
        if isinstance(node, ast.keyword) and node.arg and _ROUTER_LOOP_VOCAB.search(node.arg):
            return True
        if isinstance(node, ast.arg) and _ROUTER_LOOP_VOCAB.search(node.arg):
            return True
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [a.name for a in node.names]
            if isinstance(node, ast.ImportFrom) and node.module:
                names.append(node.module)
            if any(_ROUTER_LOOP_VOCAB.search(n) for n in names):
                return True
    return False


def _receiver_kind(ctx: ModuleCtx, call: ast.Call) -> str | None:
    """Classify the receive call's receiver: ``"queue"`` / ``"event"`` /
    ``"conn"``, else None (not provably a blocking endpoint — a ``dict``
    named ``table`` must never fire). A bare name is chased to its
    binding through the dataflow core first (``pending = queue.Queue();
    pending.get()`` still fires), then the receiver identifier itself is
    read as a fallback for attributes and parameters."""
    recv = call.func.value
    if isinstance(recv, ast.Name):
        bound = dataflow.resolve_expr(recv, ctx.scopes_at(call))
        if isinstance(bound, ast.Call):
            name = ctx.resolve(bound.func) or ""
            if not name:
                f = bound.func
                name = f.attr if isinstance(f, ast.Attribute) else (
                    f.id if isinstance(f, ast.Name) else ""
                )
            last = name.split(".")[-1]
            if _QUEUE_CTOR.search(last):
                return "queue"
            if _EVENT_CTOR.search(last):
                return "event"
            if _CONN_CTOR.search(last):
                return "conn"
    ident = recv.attr if isinstance(recv, ast.Attribute) else (
        recv.id if isinstance(recv, ast.Name) else ""
    )
    if not ident:
        return None
    if _QUEUEISH_NAME.search(ident):
        return "queue"
    if _EVENTISH_NAME.search(ident):
        return "event"
    if _CONNISH_NAME.search(ident):
        return "conn"
    return None


def _receive_is_bounded(call: ast.Call) -> bool:
    """Whether the receive carries a deadline: ``timeout=`` keyword, the
    positional timeout slot (``get(block, timeout)`` / ``wait(timeout)``),
    or — for ``recv``, which HAS no timeout form — nothing (the sanction
    for a pipe is a ``poll(timeout)`` guard, checked by the caller)."""
    for kw in call.keywords:
        if kw.arg == "timeout":
            return True
        if kw.arg is None:  # **kwargs — cannot prove it unbounded
            return True
    attr = call.func.attr
    if attr == "get":
        return len(call.args) >= 2  # get(block, timeout)
    if attr == "wait":
        return len(call.args) >= 1  # wait(timeout)
    return False  # recv() has no timeout parameter at all


def _is_queue_get_form(call: ast.Call) -> bool:
    """``.get()`` is also the dict/mapping accessor; only the queue
    SIGNATURE counts: no positional args, or a boolean ``block`` flag
    first — ``table.get(key)`` / ``cfg.get("x", default)`` never match.
    Keywords outside the ``block``/``timeout`` pair (e.g. ``default=``)
    mark a mapping accessor too."""
    if call.args and not (
        isinstance(call.args[0], ast.Constant) and isinstance(call.args[0].value, bool)
    ):
        return False
    return all(kw.arg in ("block", "timeout", None) for kw in call.keywords)


def _function_polls_receiver(ctx: ModuleCtx, call: ast.Call) -> bool:
    """Whether the enclosing function guards its ``recv()`` with a
    ``poll(timeout)`` call — the only bounded form a Connection offers."""
    scope = ctx.enclosing_function(call) or ctx.tree
    for n in ast.walk(scope):
        if (
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "poll"
            and (n.args or n.keywords)
        ):
            return True
    return False


@rule("DML213", "unbounded blocking receive in router-loop code")
def check_unbounded_blocking_receive(ctx: ModuleCtx):
    """In router-loop code (the multi-replica front door — heartbeats,
    failover, circuit breakers), a blocking receive with NO deadline —
    ``queue.get()``, ``Connection.recv()``, ``Event.wait()`` without
    ``timeout=`` — parks the loop until the far side speaks. The router's
    step loop IS the health detector: while it is parked, heartbeat
    deadlines are never evaluated, breakers never half-open, and one
    wedged replica makes every replica behind the router look dead at
    once — the exact single-point-of-failure the front door exists to
    remove. Bound every receive (``get(timeout=...)`` / ``wait(t)`` in a
    re-check loop, ``poll(t)`` before ``recv()``) or use the non-blocking
    form (``get_nowait()``). Flow-aware: a receiver is typed by chasing
    its binding to the constructor through the dataflow core
    (``pending = queue.Queue(); pending.get()`` fires no matter the
    name); ``dict.get(key)`` and other mapping accessors never match
    (queue signature required); training modules are out of scope — a
    data-plane worker blocking on its feed has no heartbeat contract to
    violate."""
    if not _module_is_router_loop(ctx):
        return
    for node in ast.walk(ctx.tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in ("get", "recv", "wait")
        ):
            continue
        if node.func.attr == "get" and not _is_queue_get_form(node):
            continue
        kind = _receiver_kind(ctx, node)
        if kind is None:
            continue
        # the attr must match the receiver's protocol: get↔queue,
        # wait↔event, recv↔conn — a queue has no .wait, an event no .get
        if (kind, node.func.attr) not in (("queue", "get"), ("event", "wait"), ("conn", "recv")):
            continue
        if _receive_is_bounded(node):
            continue
        if node.func.attr == "recv" and _function_polls_receiver(ctx, node):
            continue
        fn = ctx.enclosing_function(node)
        what = {
            "queue": "queue get", "event": "event wait", "conn": "pipe recv"
        }[kind]
        remedy = {
            "queue": "get(timeout=...) in a re-check loop, or get_nowait()",
            "event": "wait(timeout) in a re-check loop",
            "conn": "poll(timeout) before recv()",
        }[kind]
        yield _f(
            ctx, "DML213", node,
            f"unbounded blocking {what} in router-loop code: while the loop "
            "is parked here, heartbeat deadlines are never checked and "
            "breakers never half-open — one wedged replica makes them all "
            f"look dead; bound it ({remedy})",
            getattr(fn, "name", ""),
        )


# ------------------------------------------------------------------- DML215

#: identifiers that name a PER-REQUEST value — the label values that mint
#: one metric series per request. Deliberately excludes plurals and
#: generic words ("tokens" is a token array, "name" a replica name).
_REQUEST_ID_STEM = re.compile(
    r"(?i)(^|_)(rid|req|request|token|trace|uuid|session)(_?ids?)?(_|$)"
)

#: registry factory methods that create a metric family
_METRIC_CREATE_ATTRS = frozenset({"counter", "gauge", "histogram"})

#: what a metric-registry receiver looks like (``reg.counter(...)``,
#: ``self.metrics.histogram(...)``) — scopes the create-in-loop check so
#: ``np.histogram(request_latencies)`` in a loop can never match
_REGISTRY_RECV = re.compile(r"(?i)(^|_)(registry|metrics|meter|reg)$")


def _request_idish(expr: ast.AST, scopes) -> bool:
    """``expr`` carries a per-request identifier: a name/attribute in the
    request-id vocabulary, a constant-string subscript key in it
    (``rec["request_id"]``), an f-string interpolating one — or, flow-
    aware, a bare name BOUND to any of those through the dataflow core."""

    def direct(e: ast.AST) -> bool:
        for sub in ast.walk(e):
            if isinstance(sub, ast.Name) and _REQUEST_ID_STEM.search(sub.id):
                return True
            if isinstance(sub, ast.Attribute) and _REQUEST_ID_STEM.search(sub.attr):
                return True
            if (
                isinstance(sub, ast.Subscript)
                and isinstance(sub.slice, ast.Constant)
                and isinstance(sub.slice.value, str)
                and _REQUEST_ID_STEM.search(sub.slice.value)
            ):
                return True
        return False

    if direct(expr):
        return True
    if isinstance(expr, ast.Name):
        bound = dataflow.resolve_expr(expr, scopes)
        if bound is not None and bound is not expr:
            return direct(bound)
    return False


@rule("DML215", "unbounded metric label cardinality in a per-request loop")
def check_metric_label_cardinality(ctx: ModuleCtx):
    """A metrics series minted PER REQUEST: ``family.labels(...)`` inside
    a ``for``/``while`` body with a label value that resolves to a
    request id / idempotency token / trace id, or a registry
    ``counter()``/``gauge()``/``histogram()`` call in a loop whose metric
    NAME is built from one (an f-string per request = one family per
    request). Either way the registry grows with traffic and never
    shrinks — the OOM that surfaces three weeks into a deployment, and
    exactly what the registry's ``max_series`` overflow collapse exists
    to contain (telemetry/metrics_registry.py; the engine pre-binds every
    series handle in ``__init__`` for this reason). Flow-aware via the
    DML2xx dataflow core: ``key = rec["request_id"]; fam.labels(k=key)``
    still fires. Bounded label values (statuses, replica names, tenant
    tiers) and constant family names never match; functions *defined*
    inside the loop run at call time and are skipped."""

    def label_values(call: ast.Call):
        yield from call.args
        for kw in call.keywords:
            if kw.arg is not None:
                yield kw.value

    def hit(call: ast.Call) -> str | None:
        func = call.func
        if not isinstance(func, ast.Attribute):
            return None
        if func.attr == "labels" and (call.args or call.keywords):
            scopes = ctx.scopes_at(call)
            if any(_request_idish(v, scopes) for v in label_values(call)):
                return (
                    "per-request label value in a metrics .labels(...) call "
                    "inside a serve loop: every request mints a NEW series, so "
                    "the registry grows with traffic forever (cardinality is "
                    "memory); resolve the series handle once outside the loop "
                    "and label by a bounded vocabulary (status/replica/tenant "
                    "tier), as the registry's max_series collapse is a "
                    "backstop, not a design"
                )
            return None
        if func.attr in _METRIC_CREATE_ATTRS:
            recv = attr_chain(func.value)
            if not (recv and _REGISTRY_RECV.search(recv[-1])):
                return None
            name_arg = call.args[0] if call.args else next(
                (kw.value for kw in call.keywords if kw.arg == "name"), None
            )
            if name_arg is None or isinstance(name_arg, ast.Constant):
                return None  # a constant family name is registered once
            if _request_idish(name_arg, ctx.scopes_at(call)):
                return (
                    "metric family created inside a serve loop with a "
                    "per-request NAME: one family per request id is unbounded "
                    "registry growth (and every family re-renders on each "
                    "scrape); create ONE family with a constant name before "
                    "the loop and put the bounded dimension in a label"
                )
        return None

    def visit(node: ast.AST, in_loop: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                # the nested body executes when called, not per iteration
                yield from visit(child, False)
                continue
            if in_loop and isinstance(child, ast.Call):
                message = hit(child)
                if message is not None:
                    fn = ctx.enclosing_function(child)
                    yield _f(ctx, "DML215", child, message, getattr(fn, "name", ""))
            yield from visit(
                child, in_loop or isinstance(child, (ast.For, ast.AsyncFor, ast.While))
            )

    yield from visit(ctx.tree, False)
