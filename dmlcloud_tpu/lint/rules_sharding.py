"""The sharding/collective contract rules (DML201-DML204, DML207).

GSPMD-style named-axis sharding makes axis names and partition specs the
load-bearing strings of a pjit program: a typo'd ``axis_name``, a
``shard_map`` spec that doesn't match the wrapped function, or a donated
buffer read after the call all compile silently on the author's laptop and
fail — cryptically, or worse, numerically — on the TPU. These rules check
the contracts on CPU, using the dataflow core (lint/dataflow.py) to resolve
axis names through assignments and across files:

- DML201  collective whose ``axis_name`` is not a declared mesh axis, or
          missing entirely inside a ``shard_map`` body
- DML202  ``shard_map`` ``in_specs`` arity mismatch vs the wrapped
          function, or a ``PartitionSpec`` naming an unknown axis
- DML203  collective in host-side code (module level / the epoch loop) —
          outside any ``shard_map``/``jit`` trace context
- DML204  value donated to a jitted call (``donate_argnums``) read again
          after the call — the buffer no longer exists
- DML207  ``restore_state()`` without a ``template=``/``mesh=`` target in
          code that builds a mesh — the restore silently keeps the
          SAVE-time layout, wrong on the mesh built here

All of them stay silent when a value cannot be *proven* (an axis name that
is a function parameter, specs built dynamically): a linter that guesses is
a linter that gets disabled.
"""

from __future__ import annotations

import ast

from . import dataflow
from .engine import Finding, ModuleCtx, attr_chain, rule

#: jax.lax collectives that take ``axis_name`` as their second positional /
#: ``axis_name`` keyword argument
_COLLECTIVES = frozenset(
    {"psum", "pmean", "pmax", "pmin", "ppermute", "pshuffle", "all_gather", "all_to_all", "psum_scatter"}
)
#: axis-queries: first positional argument IS the axis name
_AXIS_QUERIES = frozenset({"axis_index", "axis_size"})


def _f(ctx: ModuleCtx, rule_id: str, node: ast.AST, message: str, context: str = "") -> Finding:
    return Finding(rule_id, ctx.path, node.lineno, node.col_offset, message, context)


def _lax_call_name(ctx: ModuleCtx, call: ast.Call) -> str | None:
    """'psum' for a call that provably resolves to ``jax.lax.<collective>``
    (through import aliases), else None. Requiring the ``jax.lax`` prefix
    keeps arbitrary user functions named ``psum`` out of scope."""
    resolved = ctx.resolve(call.func) or ""
    if not resolved.startswith("jax.lax."):
        return None
    last = resolved.split(".")[-1]
    if last in _COLLECTIVES or last in _AXIS_QUERIES:
        return last
    return None


def _axis_arg(call: ast.Call, name: str) -> ast.expr | None:
    """The ``axis_name`` argument expression of a collective call, or None
    when absent."""
    pos = 0 if name in _AXIS_QUERIES else 1
    if len(call.args) > pos:
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return kw.value
    return None


def _fn_context_name(ctx: ModuleCtx, node: ast.AST) -> str:
    fn = ctx.enclosing_function(node)
    return getattr(fn, "name", "") if fn is not None else ""


def _in_shard_map_body(ctx: ModuleCtx, node: ast.AST) -> bool:
    """Whether ``node`` sits inside a function (or lambda) this module
    provably hands to ``shard_map``."""
    enclosing = set(ctx.enclosing_functions(node))
    if enclosing & ctx.shard_mapped_defs:
        return True
    # lambdas aren't FunctionDefs; walk raw parents for them
    cur = ctx.parents.get(node)
    while cur is not None:
        if cur in ctx.shard_mapped_defs:
            return True
        cur = ctx.parents.get(cur)
    return False


# ------------------------------------------------------------------- DML201


@rule("DML201", "collective axis_name is not a declared mesh axis")
def check_collective_axis(ctx: ModuleCtx):
    """``psum(x, 'dta')`` compiles fine and dies on the TPU with an XLA
    unbound-axis error — or silently reduces over the wrong group when the
    typo happens to name a *different* real axis. The axis argument is
    resolved through assignments (``ax = 'data'; psum(x, ax)``) and checked
    against the mesh-axis registry: axes declared by any ``create_mesh``/
    ``parse_mesh_axes``/``Mesh`` literal in the scanned files, plus the
    framework's ``DATA``/``FSDP``/... vocabulary. Unresolvable axis
    expressions (function parameters, computed names) are never flagged. A
    collective with NO axis argument at all is flagged when it provably
    runs inside a ``shard_map`` body (there it reduces over nothing)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _lax_call_name(ctx, node)
        if name is None:
            continue
        axis_expr = _axis_arg(node, name)
        fn_name = _fn_context_name(ctx, node)
        if axis_expr is None:
            if name in _COLLECTIVES and _in_shard_map_body(ctx, node):
                yield _f(
                    ctx, "DML201", node,
                    f"jax.lax.{name} inside a shard_map body without an axis_name: "
                    "the collective reduces over no mesh axis (name the mapped "
                    "axis, e.g. axis_name='data')",
                    fn_name,
                )
            continue
        axes = dataflow.string_values(axis_expr, ctx.scopes_at(node))
        if not axes:
            continue  # unresolvable (or P(None)-style empty): do not guess
        unknown = sorted(axes - ctx.known_axes())
        if unknown:
            yield _f(
                ctx, "DML201", node,
                f"jax.lax.{name} names mesh axis {', '.join(map(repr, unknown))} "
                "which no create_mesh/parse_mesh_axes/Mesh declaration in the "
                "scanned files declares (declared: "
                f"{', '.join(sorted(ctx.known_axes()))})",
                fn_name,
            )


# ------------------------------------------------------------------- DML202


def _spec_call_axes(call: ast.Call, scopes) -> set[str] | None:
    """Axis strings a ``P(...)``/``PartitionSpec(...)`` call names (None
    entries and unresolvable elements are skipped, not failed: every
    *literal* axis string in a spec is checkable on its own)."""
    axes: set[str] = set()
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        vals = dataflow.string_values(arg, scopes)
        if vals:
            axes |= vals
    return axes


def _iter_partition_specs(ctx: ModuleCtx, expr: ast.AST, scopes):
    """Yield every ``P(...)``/``PartitionSpec(...)`` call under ``expr``,
    resolving one level of name indirection for the container itself
    (``specs = (P('data'), P(None)); shard_map(f, in_specs=specs, ...)``)."""
    expr = dataflow.resolve_expr(expr, scopes)
    for node in ast.walk(expr):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func) or ""
        last = resolved.split(".")[-1] if resolved else ""
        if last in ("P", "PartitionSpec") or resolved == "jax.sharding.PartitionSpec":
            yield node


def _shard_map_kwargs(call: ast.Call) -> dict[str, ast.expr]:
    """mesh/in_specs/out_specs of a shard_map-style call (kw or positional
    after the wrapped fn)."""
    out: dict[str, ast.expr] = {}
    names = ("mesh", "in_specs", "out_specs")
    for i, arg in enumerate(call.args[1:4]):
        out[names[i]] = arg
    for kw in call.keywords:
        if kw.arg in names:
            out[kw.arg] = kw.value
    return out


def _positional_param_count(fn: ast.AST) -> int | None:
    """How many positional parameters ``fn`` takes, or None when *args
    makes the count open-ended."""
    args = fn.args
    if args.vararg is not None:
        return None
    n = len(args.posonlyargs) + len(args.args)
    if n and not isinstance(fn, ast.Lambda):
        first = (args.posonlyargs + args.args)[0].arg
        if first in ("self", "cls"):
            n -= 1
    return n


@rule("DML202", "shard_map specs do not match the wrapped function or mesh")
def check_shard_map_specs(ctx: ModuleCtx):
    """Two contracts, both checked flow-aware: (1) a tuple-literal
    ``in_specs`` must have one spec per positional parameter of the wrapped
    function — a mismatch is a cryptic tree-structure error at trace time;
    (2) every axis a ``PartitionSpec`` names must exist. When the ``mesh``
    argument resolves to a local axes literal (``mesh = create_mesh({'data':
    2})``) the spec axes are checked against THAT mesh exactly; otherwise
    against the project-wide registry."""
    for call in ctx.shard_map_calls:
        kwargs = _shard_map_kwargs(call)
        scopes = ctx.scopes_at(call)
        fn_name = _fn_context_name(ctx, call)

        # the wrapped function (for the arity check)
        wrapped = None
        if call.args:
            target = call.args[0]
            if isinstance(target, ast.Lambda):
                wrapped = target
            elif isinstance(target, ast.Name):
                for d in ctx.shard_mapped_defs:
                    if getattr(d, "name", None) == target.id:
                        wrapped = d
                        break

        in_specs = kwargs.get("in_specs")
        if in_specs is not None and wrapped is not None:
            resolved_specs = dataflow.resolve_expr(in_specs, scopes)
            if isinstance(resolved_specs, (ast.Tuple, ast.List)):
                n_params = _positional_param_count(wrapped)
                n_specs = len(resolved_specs.elts)
                if n_params is not None and n_specs != n_params:
                    wname = getattr(wrapped, "name", "<lambda>")
                    yield _f(
                        ctx, "DML202", call,
                        f"shard_map in_specs has {n_specs} spec(s) but "
                        f"{wname!r} takes {n_params} positional argument(s); "
                        "every argument needs exactly one spec",
                        fn_name,
                    )

        # the axis universe: a locally-resolvable mesh literal beats the
        # global registry (this is where 'model' on a data-only mesh is caught)
        universe: set[str] | None = None
        mesh_expr = kwargs.get("mesh")
        if mesh_expr is not None:
            resolved_mesh = dataflow.resolve_expr(mesh_expr, scopes)
            if isinstance(resolved_mesh, ast.Call):
                universe = dataflow.axes_from_call(resolved_mesh, ctx, scopes)
        if universe is None:
            universe = ctx.known_axes()

        seen: set[tuple[int, int]] = set()
        for key in ("in_specs", "out_specs"):
            expr = kwargs.get(key)
            if expr is None:
                continue
            for spec_call in _iter_partition_specs(ctx, expr, scopes):
                axes = _spec_call_axes(spec_call, scopes)
                unknown = sorted(axes - universe) if axes else []
                loc = (spec_call.lineno, spec_call.col_offset)
                if unknown and loc not in seen:
                    seen.add(loc)
                    yield Finding(
                        "DML202", ctx.path, call.lineno, call.col_offset,
                        f"shard_map {key} names mesh axis "
                        f"{', '.join(map(repr, unknown))} not present on the mesh "
                        f"(axes: {', '.join(sorted(universe))})",
                        fn_name,
                    )


# ------------------------------------------------------------------- DML203


@rule("DML203", "collective in host-side code outside any trace context")
def check_collective_outside_trace(ctx: ModuleCtx):
    """``jax.lax.psum`` only means something under a mapped axis — inside a
    ``shard_map``/``pmap`` body or a jitted function that provides the axis.
    At module level or in the host-side epoch loop it raises a NameError-
    style unbound-axis error at runtime (after the import, possibly on the
    pod). Only provably-host contexts are flagged: module top level and
    ``run_epoch``/``train_epoch``/``val_epoch`` bodies — a plain helper
    function may legitimately be *called* from traced code (ring_attention's
    entry points are exactly that) and stays silent."""
    step_nodes = {fc.node for fc in ctx.step_fns}
    epoch_nodes = {fc.node for fc in ctx.epoch_fns}
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = _lax_call_name(ctx, node)
        if name is None or name in _AXIS_QUERIES:
            continue
        enclosing = ctx.enclosing_functions(node)
        if not enclosing:
            yield _f(
                ctx, "DML203", node,
                f"jax.lax.{name} at module level runs eagerly outside any "
                "shard_map/jit trace — there is no mapped axis to reduce over",
            )
            continue
        if set(enclosing) & (step_nodes | ctx.shard_mapped_defs):
            continue
        if enclosing[-1] in epoch_nodes or enclosing[0] in epoch_nodes:
            yield _f(
                ctx, "DML203", node,
                f"jax.lax.{name} in the host-side epoch loop: collectives only "
                "exist under a mapped axis (move it into the traced step, or "
                "use parallel.runtime's host collectives for control-plane data)",
                _fn_context_name(ctx, node),
            )


# ------------------------------------------------------------------- DML204


def _call_target_name(call: ast.Call) -> str | None:
    """Dotted name of the called object ('train' or 'self._step')."""
    if isinstance(call.func, ast.Name):
        return call.func.id
    if isinstance(call.func, ast.Attribute):
        chain = attr_chain(call.func)
        if chain:
            return ".".join(chain)
    return None


def _expr_key(node: ast.AST) -> str | None:
    """Dotted key of a Name/attribute-chain expression ('state', 'self.state')."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        chain = attr_chain(node)
        if chain and all(p.isidentifier() for p in chain):
            return ".".join(chain)
    return None


def _stmt_rebinds(stmt: ast.AST, key: str) -> bool:
    """Whether the statement assigns ``key`` (Name or attribute chain)."""
    targets: list[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        targets = [stmt.target]
    for tgt in targets:
        for node in ast.walk(tgt):
            if _expr_key(node) == key:
                return True
    return False


def _enclosing_stmt(ctx: ModuleCtx, node: ast.AST, within: ast.AST) -> ast.AST:
    """The outermost simple statement containing ``node`` below ``within``."""
    stmt = node
    cur = ctx.parents.get(node)
    while cur is not None and cur is not within:
        if isinstance(cur, (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Expr, ast.Return, ast.For, ast.AsyncFor, ast.While, ast.If, ast.With)):
            stmt = cur
            if isinstance(cur, (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Expr, ast.Return)):
                break
        cur = ctx.parents.get(cur)
    return stmt


@rule("DML204", "value read again after being donated to a jitted call")
def check_use_after_donate(ctx: ModuleCtx):
    """``donate_argnums`` hands the argument's buffers to XLA: after the
    call they are deleted, and the next read raises
    ``RuntimeError: Array has been deleted`` — at RUNTIME, often only on
    the TPU where donation actually rebinds memory. Tracked per function:
    a call through a name bound to ``jax.jit(..., donate_argnums=...)``
    marks the donated argument names dead from the end of that statement
    until they are reassigned; any read in between is flagged. The standard
    idiom ``state = step(state, batch)`` rebinds in the same statement and
    is fine. A donating call inside a loop whose donated argument is never
    rebound in that loop is flagged at the call: iteration 2 re-passes the
    deleted buffer."""
    if not ctx.donating_names:
        return
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            target = _call_target_name(node)
            if target is None:
                continue
            donated = ctx.donating_names.get(target) or ctx.donating_names.get(target.split(".")[-1])
            if not donated:
                continue
            call_stmt = _enclosing_stmt(ctx, node, fn)
            end_line = getattr(call_stmt, "end_lineno", node.lineno)
            for idx in sorted(donated):
                if idx >= len(node.args):
                    continue
                key = _expr_key(node.args[idx])
                if key is None:
                    continue
                if _stmt_rebinds(call_stmt, key):
                    # `state = step(state, batch)` — donated AND rebound: safe.
                    # But inside a loop the rebind must target the SAME name,
                    # which it does here by construction.
                    continue
                # loop hazard: the call re-runs with a deleted buffer
                loop = None
                cur = ctx.parents.get(node)
                while cur is not None and cur is not fn:
                    if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                        loop = cur
                        break
                    cur = ctx.parents.get(cur)
                if loop is not None and not any(
                    _stmt_rebinds(s, key) for s in ast.walk(loop) if s is not call_stmt
                ):
                    yield _f(
                        ctx, "DML204", node,
                        f"{key!r} is donated to {target!r} inside this loop but "
                        "never rebound: the next iteration passes a deleted "
                        "buffer (rebind it, e.g. `"
                        f"{key} = {target}({key}, ...)`)",
                        getattr(fn, "name", ""),
                    )
                    continue
                # linear hazard: first read after the donating statement,
                # before any rebind
                rebind_line = None
                for stmt in ast.walk(fn):
                    if (
                        getattr(stmt, "lineno", 0) > end_line
                        and _stmt_rebinds(stmt, key)
                        and (rebind_line is None or stmt.lineno < rebind_line)
                    ):
                        rebind_line = stmt.lineno
                first_read = None
                for read in ast.walk(fn):
                    if not isinstance(read, (ast.Name, ast.Attribute)):
                        continue
                    if not isinstance(getattr(read, "ctx", None), ast.Load):
                        continue
                    if _expr_key(read) != key:
                        continue
                    line = getattr(read, "lineno", 0)
                    if line <= end_line:
                        continue
                    if rebind_line is not None and line > rebind_line:
                        continue
                    if first_read is None or line < first_read.lineno:
                        first_read = read
                if first_read is not None:
                    yield _f(
                        ctx, "DML204", first_read,
                        f"{key!r} was donated to {target!r} on line "
                        f"{node.lineno} (donate_argnums): its buffers are "
                        "deleted — reading it here raises at runtime. Use the "
                        "call's result instead, or drop the donation",
                        getattr(fn, "name", ""),
                    )


# ------------------------------------------------------------------- DML207


def _builds_mesh(ctx: ModuleCtx, container: ast.AST) -> bool:
    """Whether any call under ``container`` provably resolves to a mesh
    builder (``create_mesh``/``auto_mesh``/``set_mesh``/``Mesh``/
    ``parse_mesh_axes``) — the dataflow core's notion of mesh-declaring
    code, reused as DML207's notion of mesh-BUILDING code."""
    for node in ast.walk(container):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func) or ""
        last = resolved.split(".")[-1] if resolved else ""
        if not last and isinstance(node.func, ast.Attribute):
            last = node.func.attr
        if last in dataflow._MESH_BUILDERS:
            return True
    return False


@rule("DML207", "restore_state without a template/mesh target in mesh-building code")
def check_untargeted_restore(ctx: ModuleCtx):
    """``ckpt.restore_state()`` with neither ``template=`` nor ``mesh=``
    hands back arrays in the SAVE-time layout. In code that builds its own
    mesh that is almost never what runs next: the restored state silently
    mismatches the mesh built here, compiles fine on CPU, and fails (or
    silently double-pays resharding) only on the TPU pod. Flow-aware: a
    ``template`` argument that provably resolves to ``None`` (``tpl = None;
    ckpt.restore_state(1, tpl)``) counts as absent, an unresolvable one is
    trusted; code whose enclosing function (or, at module level, module)
    never provably builds a mesh stays silent — a helper restoring for
    host-side analysis is legitimate. Fix: pass ``mesh=<the mesh built
    here>`` for the elastic resharded restore (doc/elasticity.md), or an
    explicit template."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else None
        )
        if name != "restore_state":
            continue
        scopes = ctx.scopes_at(node)
        target_expr = node.args[1] if len(node.args) > 1 else None
        targeted = False
        for kw in node.keywords:
            if kw.arg in ("template", "mesh"):
                target_expr = kw.value
            elif kw.arg is None:
                targeted = True  # **kwargs: cannot prove the target absent
        if target_expr is not None:
            resolved = dataflow.resolve_expr(target_expr, scopes)
            if not (isinstance(resolved, ast.Constant) and resolved.value is None):
                targeted = True
        if targeted:
            continue
        fn = ctx.enclosing_function(node)
        if not _builds_mesh(ctx, fn if fn is not None else ctx.tree):
            continue
        yield _f(
            ctx, "DML207", node,
            "restore_state() without template= or mesh= in code that builds "
            "a mesh: the restore keeps the SAVE-time sharding layout, which "
            "silently mismatches the mesh built here and fails only on the "
            "TPU — pass mesh=<the current mesh> (resharded restore) or an "
            "explicit template",
            _fn_context_name(ctx, node),
        )
