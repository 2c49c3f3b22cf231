"""The TPU-hazard rules (DML101-DML108).

Each rule enforces one clause of the overlap engine's sync-point contract
(doc/performance.md §3, doc/lint.md for the full catalog with examples):

- DML101  host sync inside step/epoch code (defeats ``deferred_metrics()``)
- DML102  Python/NumPy RNG inside a jitted step fn (breaks the seed story)
- DML103  jitted train-step without donated train state (HBM bloat)
- DML104  retrace/unroll hazards in a jitted step fn
- DML105  blocking checkpoint/wandb calls inside the epoch loop
- DML106  wall-clock timing of async dispatches without a device sync
- DML107  jax.jit / pjit call inside a loop body (defeats the jit cache)
- DML108  time.time() for step timing in step/epoch code (not monotonic)

Rules yield raw findings; the engine applies suppressions and sorting.
"""

from __future__ import annotations

import ast

from .engine import (
    Finding,
    ModuleCtx,
    attr_chain,
    expr_tainted,
    is_stall_accounted,
    rule,
    walk_fn,
)

_NUMPY_SYNC_FNS = frozenset({"numpy.asarray", "numpy.array"})

#: calls whose result is static under trace — branching through them is safe
_TRACE_SAFE_CALLS = frozenset(
    {"isinstance", "issubclass", "len", "hasattr", "callable", "getattr", "type"}
)
#: attributes that are static under trace (shape/dtype metadata)
_TRACE_SAFE_ATTRS = frozenset({"shape", "ndim", "dtype", "size"})

_WALL_CLOCK_FNS = frozenset(
    {
        "time.time",
        "time.time_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.monotonic",
        "time.monotonic_ns",
    }
)
#: call attr names that prove the timed region was closed with a device sync
_SYNC_MARKERS = frozenset({"block_until_ready", "block", "item", "device_get"})

_SAVE_ATTRS = frozenset({"save", "save_state", "save_checkpoint", "save_pytree"})


def _f(ctx: ModuleCtx, rule_id: str, node: ast.AST, message: str, context: str) -> Finding:
    return Finding(rule_id, ctx.path, node.lineno, node.col_offset, message, context)


# ------------------------------------------------------------------- DML101


@rule("DML101", "host sync inside step/epoch code")
def check_host_sync(ctx: ModuleCtx):
    """``.item()``, ``jax.device_get``, ``float()``/``np.asarray()`` on
    traced values, and ``print`` inside step/epoch code. Exempt: anything
    under ``with <x>.measure():`` (a StallTimer-accounted block) and
    stall-timer ``fetch``/``block`` calls — accounted syncs are the
    framework's sanctioned pattern, unaccounted ones defeat
    ``deferred_metrics()``."""
    for fn in ctx.step_fns + ctx.epoch_fns:
        is_step = fn.kind == "step"
        for node, in_measure in walk_fn(fn.node):
            if in_measure or not isinstance(node, ast.Call):
                continue
            if is_stall_accounted(node):
                continue
            func = node.func
            arg = node.args[0] if node.args else None

            if isinstance(func, ast.Attribute) and func.attr == "item" and not node.args:
                yield _f(
                    ctx, "DML101", node,
                    ".item() forces a host sync; keep the value on device and "
                    "track it (the tracker reduces once per epoch)",
                    fn.qualname,
                )
                continue

            resolved = ctx.resolve(func) or ""
            if resolved == "jax.device_get":
                yield _f(
                    ctx, "DML101", node,
                    "jax.device_get blocks on the dispatch queue; defer the "
                    "readback to a sync point or time it under StallTimer.measure()",
                    fn.qualname,
                )
                continue
            if is_step and resolved == "jax.block_until_ready":
                yield _f(
                    ctx, "DML101", node,
                    "block_until_ready inside a traced step is a per-step "
                    "host sync; sync once at the epoch boundary instead",
                    fn.qualname,
                )
                continue
            if resolved in _NUMPY_SYNC_FNS and arg is not None:
                hazard = (
                    expr_tainted(arg, fn.tainted)
                    if is_step
                    else isinstance(arg, (ast.Name, ast.Attribute, ast.Subscript))
                )
                if hazard:
                    yield _f(
                        ctx, "DML101", node,
                        f"{resolved.split('.')[-1]}() on a device value copies it "
                        "to host synchronously; use StallTimer.fetch() or defer "
                        "to the epoch-end reduce",
                        fn.qualname,
                    )
                continue
            if isinstance(func, ast.Name) and func.id not in ctx.aliases:
                if is_step and func.id in ("float", "int", "bool") and arg is not None:
                    if expr_tainted(arg, fn.tainted):
                        yield _f(
                            ctx, "DML101", node,
                            f"{func.id}() on a traced value concretizes it (host "
                            "sync / ConcretizationTypeError); return it and track "
                            "it on device",
                            fn.qualname,
                        )
                    continue
                if not is_step and func.id == "float" and isinstance(
                    arg, (ast.Name, ast.Subscript)
                ):
                    yield _f(
                        ctx, "DML101", node,
                        "float() on a per-step metric blocks the epoch loop; "
                        "fetch at a log_every() boundary via StallTimer.fetch() "
                        "or track the device value",
                        fn.qualname,
                    )
                    continue
                if is_step and func.id == "print":
                    yield _f(
                        ctx, "DML101", node,
                        "print inside a traced step fires at trace time (or "
                        "syncs on concrete values); use jax.debug.print or log "
                        "at a sync point",
                        fn.qualname,
                    )


# ------------------------------------------------------------------- DML102


@rule("DML102", "Python/NumPy RNG inside a jitted step fn")
def check_host_rng(ctx: ModuleCtx):
    """``random.*`` / ``np.random.*`` in traced code runs once at trace
    time: every execution reuses the same "random" constant, silently
    breaking reproducibility AND randomness. Use ``jax.random`` with a key
    derived from the state (``jax.random.fold_in(state.rng, state.step)``)."""
    for fn in ctx.step_fns:
        for node, _ in walk_fn(fn.node):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.resolve(node.func) or ""
            if resolved.startswith("numpy.random."):
                yield _f(
                    ctx, "DML102", node,
                    f"{resolved} inside a jitted step is baked in at trace time "
                    "(not random, not reproducible); use jax.random with a key "
                    "from the state",
                    fn.qualname,
                )
            elif resolved.startswith("random."):
                yield _f(
                    ctx, "DML102", node,
                    f"stdlib {resolved} inside a jitted step is baked in at "
                    "trace time; use jax.random with a key from the state",
                    fn.qualname,
                )


# ------------------------------------------------------------------- DML103


def _is_trainish(name: str | None) -> bool:
    if not name:
        return False
    n = name.lower()
    return ("train" in n and ("step" in n or "update" in n)) or n in (
        "update_step",
        "update_fn",
    )


@rule("DML103", "jitted train-step without donated train state")
def check_donation(ctx: ModuleCtx):
    """A train step that does not donate its input state keeps two copies
    of params+optimizer state live across the update — HBM bloat that halves
    the largest fittable model. ``jax.jit(train_step, donate_argnums=0)``."""
    for site in ctx.jit_sites:
        if not _is_trainish(site.target_name):
            continue
        if "donate_argnums" in site.kwargs or "donate_argnames" in site.kwargs:
            continue
        yield Finding(
            "DML103",
            ctx.path,
            site.lineno,
            site.col,
            f"jitted train step '{site.target_name}' does not donate its input "
            "state (donate_argnums/donate_argnames): params + optimizer state "
            "are held twice across the update",
            site.target_name or "",
        )


# ------------------------------------------------------------------- DML104


def _hazardous_test(node: ast.AST, tainted: set[str], ctx: ModuleCtx) -> bool:
    """A traced-value reference in a branch condition that is NOT statically
    safe. Pruned as safe: ``x is None`` checks, ``isinstance``/``len``/...
    calls, and ``.shape``/``.ndim``/``.dtype``/``.size`` metadata."""
    if isinstance(node, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
    ):
        operands = [node.left, *node.comparators]
        if any(isinstance(o, ast.Constant) and o.value is None for o in operands):
            return False
    if isinstance(node, ast.Compare) and all(
        isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
    ):
        # '"mask" in batch': pytree STRUCTURE is static under trace, so key
        # membership branches once at trace time — the idiom masked/bucketed
        # steps use (compile/buckets.py)
        if isinstance(node.left, ast.Constant) and isinstance(node.left.value, str):
            return False
    if isinstance(node, ast.Call):
        fname = (ctx.resolve(node.func) or "").split(".")[-1]
        if fname in _TRACE_SAFE_CALLS:
            return False
    if isinstance(node, ast.Attribute) and node.attr in _TRACE_SAFE_ATTRS:
        return False
    if isinstance(node, ast.Name):
        return node.id in tainted
    return any(_hazardous_test(c, tainted, ctx) for c in ast.iter_child_nodes(node))


@rule("DML104", "retrace/unroll hazard in a jitted step fn")
def check_retrace(ctx: ModuleCtx):
    """Data-dependent Python control flow on traced values either fails to
    trace or (via weak-type/shape churn and scalar closures) retraces every
    step — each retrace is a full XLA compile. Use ``jnp.where``/
    ``lax.cond``/``lax.scan``. Runtime companion: ``lint.TraceGuard`` reads
    the jit cache size across calls and catches what static analysis can't."""
    for fn in ctx.step_fns:
        for node, _ in walk_fn(fn.node):
            if isinstance(node, (ast.If, ast.While)) and _hazardous_test(
                node.test, fn.tainted, ctx
            ):
                kind = "if" if isinstance(node, ast.If) else "while"
                yield _f(
                    ctx, "DML104", node,
                    f"data-dependent `{kind}` on a traced value inside a jitted "
                    "step (trace error or per-step retrace); use jnp.where / "
                    "lax.cond",
                    fn.qualname,
                )
            elif isinstance(node, ast.IfExp) and _hazardous_test(
                node.test, fn.tainted, ctx
            ):
                yield _f(
                    ctx, "DML104", node,
                    "data-dependent conditional expression on a traced value "
                    "inside a jitted step; use jnp.where",
                    fn.qualname,
                )
            elif isinstance(node, (ast.For, ast.AsyncFor)) and _hazardous_test(
                node.iter, fn.tainted, ctx
            ):
                yield _f(
                    ctx, "DML104", node,
                    "iterating a traced value inside a jitted step unrolls the "
                    "trace (compile time scales with length); use lax.scan / "
                    "vmap",
                    fn.qualname,
                )


# ------------------------------------------------------------------- DML105


@rule("DML105", "blocking checkpoint/wandb call inside the epoch loop")
def check_blocking_io(ctx: ModuleCtx):
    """Checkpoint saves and wandb calls on the training thread stall the
    dispatch queue for the full serialization/HTTP round trip. Route saves
    through the stage's async single-flight path (``checkpoint_every*``,
    committed under ``StallTimer.measure()``) and log metrics via the
    tracker (wandb publishes once per epoch in ``_post_epoch``)."""
    for fn in ctx.epoch_fns:
        for node, in_measure in walk_fn(fn.node):
            if in_measure or not isinstance(node, ast.Call):
                continue
            resolved = ctx.resolve(node.func) or ""
            if resolved == "wandb" or resolved.startswith("wandb."):
                yield _f(
                    ctx, "DML105", node,
                    f"{resolved}() inside the epoch loop blocks training on "
                    "network I/O; track metrics instead (the pipeline publishes "
                    "to wandb once per epoch)",
                    fn.qualname,
                )
                continue
            func = node.func
            if (
                isinstance(func, ast.Attribute)
                and func.attr in _SAVE_ATTRS
                and any(
                    "ckpt" in seg.lower() or "checkpoint" in seg.lower()
                    for seg in attr_chain(func)[:-1]
                )
            ):
                yield _f(
                    ctx, "DML105", node,
                    f"blocking {func.attr}() inside the epoch loop; use the "
                    "stage's async checkpoint path (checkpoint_every_steps / "
                    "async_checkpoint) or account it under StallTimer.measure()",
                    fn.qualname,
                )


# ------------------------------------------------------------------- DML106


@rule("DML106", "wall-clock timing of dispatches without block_until_ready")
def check_dishonest_timing(ctx: ModuleCtx):
    """Under async dispatch a jitted call returns as soon as the work is
    *enqueued*; wall-clocking it without ``block_until_ready`` measures host
    enqueue cost, not device time — the classic mis-benchmark. Applies to
    any function that reads the clock twice around dispatchy calls."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        clock_reads: list[ast.Call] = []
        dispatchy = False
        synced = False
        for sub in ast.walk(node):
            # nested defs are analyzed on their own walk(ctx.tree) visit,
            # but their bodies still belong to this timing region too, so
            # they are NOT excluded here.
            if not isinstance(sub, ast.Call):
                continue
            resolved = ctx.resolve(sub.func) or ""
            if resolved in _WALL_CLOCK_FNS:
                clock_reads.append(sub)
                continue
            last = resolved.split(".")[-1] if resolved else ""
            if isinstance(sub.func, ast.Attribute):
                last = sub.func.attr
            elif isinstance(sub.func, ast.Name):
                last = sub.func.id
            if last in _SYNC_MARKERS or resolved == "jax.block_until_ready":
                synced = True
            elif (
                isinstance(sub.func, ast.Name)
                and sub.func.id == "float"
                and len(sub.args) == 1
            ):
                # a value fetch (`float(loss)`) forces the whole dependency
                # chain — the completion sync on platforms where
                # block_until_ready is unreliable
                synced = True
            elif "step" in last.lower() or last in ctx.jitted_names:
                dispatchy = True
        if len(clock_reads) >= 2 and dispatchy and not synced:
            yield _f(
                ctx, "DML106", clock_reads[1],
                "wall-clock timing around dispatched device work without "
                "block_until_ready measures enqueue cost, not execution; call "
                "jax.block_until_ready(result) before reading the clock",
                node.name,
            )


# ------------------------------------------------------------------- DML107


@rule("DML108", "time.time() used for step timing in step/epoch code")
def check_wall_clock_step_timing(ctx: ModuleCtx):
    """``time.time()`` reads the WALL clock, which NTP slews and steps —
    a few-ms jump is routine, a leap-second or chrony correction can move
    it by seconds in either direction, and every span/step duration derived
    from it is then silently wrong (negative durations crash trace viewers;
    inflated ones send you hunting a stall that never happened). Step and
    epoch code must time with ``time.perf_counter()`` /
    ``time.perf_counter_ns()`` — monotonic, and what the telemetry journal's
    own span durations use (wall clock appears only as the journal's one
    mergeable anchor per run). Outside the hazard contexts (logging a
    human-readable start time, naming a checkpoint dir) ``time.time()`` is
    fine and not flagged."""
    for fn in ctx.step_fns + ctx.epoch_fns:
        for node, _ in walk_fn(fn.node):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.resolve(node.func) or ""
            if resolved in ("time.time", "time.time_ns"):
                yield _f(
                    ctx, "DML108", node,
                    f"{resolved}() is wall-clock (NTP can step it mid-run, corrupting "
                    "step/span durations); time step and epoch code with the monotonic "
                    "time.perf_counter()/perf_counter_ns()",
                    fn.qualname,
                )


@rule("DML107", "jax.jit/pjit call inside a loop body")
def check_jit_in_loop(ctx: ModuleCtx):
    """``jax.jit(...)`` (or ``pjit`` / ``partial(jax.jit, ...)`` / a
    ``@jax.jit``-decorated ``def``) executed inside a ``for``/``while`` body
    creates a FRESH jitted callable every iteration — each one starts with
    an empty compilation cache, so every iteration re-traces and re-compiles
    work the previous iteration already paid for (the persistent cache can
    soften the XLA half, never the trace half). Hoist the ``jit`` out of the
    loop (or precompile it: compile/aot.py). Bodies of functions *defined*
    inside the loop run at call time, not per iteration, and are skipped."""

    def visit(node: ast.AST, in_loop: bool):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if in_loop:
                    for dec in child.decorator_list:
                        if ctx._jit_kwargs(dec) is not None:
                            yield _f(
                                ctx, "DML107", dec,
                                f"@jit-decorated def {child.name!r} inside a loop "
                                "body re-jits (and re-compiles) every iteration; "
                                "define it once before the loop",
                                child.name,
                            )
                # the nested body executes when called, not per iteration
                yield from visit(child, False)
                continue
            if isinstance(child, ast.Lambda):
                yield from visit(child, False)
                continue
            if in_loop and isinstance(child, ast.Call) and ctx._jit_call_kwargs(child) is not None:
                yield _f(
                    ctx, "DML107", child,
                    "jax.jit/pjit call inside a loop body builds a fresh jitted "
                    "function (empty cache) every iteration — every step re-traces "
                    "and re-compiles; hoist the jit out of the loop",
                    "",
                )
            yield from visit(
                child, in_loop or isinstance(child, (ast.For, ast.AsyncFor, ast.While))
            )

    yield from visit(ctx.tree, False)
