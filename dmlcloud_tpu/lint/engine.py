"""Core of ``dmlcloud_tpu.lint``: AST contexts, suppression comments, the
rule registry, and the lint entry points.

The linter is pure stdlib (``ast`` + ``tokenize``) — it runs on CPU with no
jax import, which is exactly where this framework's performance regressions
have to be caught (tier-1 CI runs under ``JAX_PLATFORMS=cpu``, review
happens on laptops). Rules fire only inside the *hazard contexts* the
overlap engine cares about, so a data-loading helper full of ``np.random``
and ``float()`` lints clean:

- **step context** — code that runs under an XLA trace: ``step`` /
  ``train_step`` / ``val_step`` methods of ``*Stage`` classes, any function
  decorated with ``jax.jit``/``pjit`` (incl. ``functools.partial(jax.jit,
  ...)``), and local functions passed to a ``jax.jit(...)`` call. Parameters
  named in ``static_argnums``/``static_argnames`` are *not* treated as
  traced.
- **epoch context** — the host-side hot loop: ``run_epoch`` /
  ``train_epoch`` / ``val_epoch`` methods of ``*Stage`` classes.

Host blocks that the overlap engine *accounts for* are sanctioned: anything
lexically inside a ``with <x>.measure():`` block, and ``fetch``/``block``
calls on a stall-timer receiver (``utils.profiling.StallTimer``), never
fire DML101/DML105.

Suppression comments (all forms take a comma list of rule ids or ``all``)::

    x = loss.item()  # dmllint: disable=DML101 -- eager bisection path
    # dmllint: disable-next-line=DML101,DML104
    # dmllint: disable-file=DML106

Everything after the id list is free-form justification.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Iterator

from . import dataflow

__all__ = [
    "Finding",
    "LintError",
    "RULES",
    "PROJECT_RULES",
    "rule",
    "project_rule",
    "expand_rule_ids",
    "lint_source",
    "lint_file",
    "lint_paths",
    "build_project_context",
    "ModuleCtx",
    "FnCtx",
]

#: methods of *Stage classes whose bodies run under an XLA trace
STEP_METHODS = frozenset({"step", "train_step", "val_step"})
#: methods of *Stage classes that form the host-side epoch hot loop
EPOCH_METHODS = frozenset({"run_epoch", "train_epoch", "val_epoch"})

_JIT_NAMES = frozenset(
    {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit", "jax.experimental.jit"}
)
_PARTIAL_NAMES = frozenset({"functools.partial", "partial"})

#: id of the pseudo-rule emitted for files the linter cannot parse
PARSE_ERROR_RULE = "DML999"


class LintError(Exception):
    """Raised by ``TrainingPipeline(lint="error")`` when a registered stage
    has findings; carries them on ``.findings``."""

    def __init__(self, message: str, findings: list["Finding"] | None = None):
        super().__init__(message)
        self.findings = findings or []


@dataclass(frozen=True)
class Finding:
    """One lint hit. ``context`` is the dotted function/method the finding
    is inside ('' for module level)."""

    rule: str
    path: str
    line: int
    col: int
    message: str
    context: str = ""

    def format(self) -> str:
        where = f" [{self.context}]" if self.context else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}{where}"

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "context": self.context,
        }

    def sort_key(self):
        return (self.path, self.line, self.col, self.rule, self.message)


@dataclass
class RuleInfo:
    id: str
    title: str
    check: Callable[["ModuleCtx"], Iterator[Finding]]


#: rule id -> RuleInfo; populated by the ``@rule`` decorator (rules.py)
RULES: dict[str, RuleInfo] = {}


def rule(rule_id: str, title: str):
    """Register a rule function ``check(ctx) -> Iterator[Finding]``."""

    def deco(fn):
        RULES[rule_id] = RuleInfo(rule_id, title, fn)
        return fn

    return deco


#: project-pass rule id -> RuleInfo; populated by ``@project_rule``
#: (lifecycle.py). These run once per ``lint_paths`` call over the whole
#: :class:`~dmlcloud_tpu.lint.callgraph.ProjectGraph`, never per file —
#: ``lint_source``/``lint_file`` cannot see them by construction.
PROJECT_RULES: dict[str, RuleInfo] = {}


def project_rule(rule_id: str, title: str):
    """Register a whole-program rule ``check(graph) -> Iterator[Finding]``
    taking a :class:`~dmlcloud_tpu.lint.callgraph.ProjectGraph`."""

    def deco(fn):
        PROJECT_RULES[rule_id] = RuleInfo(rule_id, title, fn)
        return fn

    return deco


#: IR-pass rule id -> RuleInfo; populated by ``@ir_rule`` (rules_ir.py).
#: These run over TRACED programs (jaxpr + compiled artifact), never over
#: source — the AST/dataflow/call-graph passes cannot see them by
#: construction. The checks themselves are stdlib-only (they duck-type the
#: traced artifacts); only the tracer in :mod:`~dmlcloud_tpu.lint.ir`
#: imports jax, so this registry keeps the package import jax-free.
IR_RULES: dict[str, RuleInfo] = {}


def ir_rule(rule_id: str, title: str):
    """Register an IR rule ``check(program) -> Iterator[Finding]`` taking a
    :class:`~dmlcloud_tpu.lint.ir.TracedProgram`."""

    def deco(fn):
        IR_RULES[rule_id] = RuleInfo(rule_id, title, fn)
        return fn

    return deco


def _id_matches(rule_id: str, spec: str) -> bool:
    """Whether ``spec`` selects ``rule_id``: exact id, ``all``, or a family
    wildcard like ``DML2xx`` (trailing ``xx`` matches any digits)."""
    if spec == "all" or spec == rule_id:
        return True
    if spec.endswith("xx") and len(spec) > 2:
        return rule_id.startswith(spec[:-2])
    return False


def expand_rule_ids(ids: Iterable[str]) -> tuple[list[str], list[str]]:
    """Expand exact ids and ``DML2xx`` family wildcards against the
    registry. Returns ``(expanded, unknown)`` — a wildcard matching nothing
    and an unregistered exact id both land in ``unknown``."""
    expanded: list[str] = []
    unknown: list[str] = []
    all_ids = sorted(set(RULES) | set(PROJECT_RULES) | set(IR_RULES))
    for spec in ids:
        matched = [rid for rid in all_ids if _id_matches(rid, spec)]
        if matched:
            expanded.extend(m for m in matched if m not in expanded)
        else:
            unknown.append(spec)
    return expanded, unknown


# --------------------------------------------------------------- suppressions

_DIRECTIVE = re.compile(
    r"#\s*dmllint:\s*(disable|disable-next-line|disable-file)\s*=\s*"
    r"([A-Za-z0-9_]+(?:\s*,\s*[A-Za-z0-9_]+)*)"
)


class Suppressions:
    """Per-line and file-wide suppression sets parsed from comments."""

    def __init__(self):
        self.by_line: dict[int, set[str]] = {}
        self.file_wide: set[str] = set()

    def is_suppressed(self, finding: Finding) -> bool:
        ids = self.by_line.get(finding.line, set()) | self.file_wide
        # family wildcards (``disable=DML2xx``) suppress the whole family
        return any(_id_matches(finding.rule, spec) for spec in ids)

    @classmethod
    def parse(cls, source: str) -> "Suppressions":
        sup = cls()
        try:
            tokens = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _DIRECTIVE.search(tok.string)
                if not m:
                    continue
                kind = m.group(1)
                ids = {p.strip() for p in m.group(2).split(",") if p.strip()}
                line = tok.start[0]
                if kind == "disable":
                    sup.by_line.setdefault(line, set()).update(ids)
                elif kind == "disable-next-line":
                    sup.by_line.setdefault(line + 1, set()).update(ids)
                else:  # disable-file
                    sup.file_wide.update(ids)
        except tokenize.TokenError:
            pass  # the ast parse reports the real syntax problem
        return sup


# ------------------------------------------------------------------ contexts


@dataclass
class FnCtx:
    """One function in a hazard context."""

    node: ast.AST  # FunctionDef | AsyncFunctionDef
    kind: str  # "step" | "epoch"
    qualname: str
    #: names carrying traced values (step contexts only): non-static
    #: parameters plus everything assigned from them
    tainted: set[str] = field(default_factory=set)


@dataclass
class JitSite:
    """One ``jax.jit``/``pjit`` call or decorator."""

    node: ast.AST  # the Call/decorator expression, for the location
    target_name: str | None  # name of the function being jitted
    kwargs: dict[str, ast.expr]
    lineno: int
    col: int


class ModuleCtx:
    """Everything the rules need about one parsed module. ``project`` is the
    optional cross-file :class:`dataflow.ProjectContext` a ``lint_paths``
    run shares between modules (mesh axes declared anywhere legitimise
    collectives everywhere)."""

    def __init__(
        self,
        path: str,
        source: str,
        tree: ast.Module,
        project: "dataflow.ProjectContext | None" = None,
        axes_only: bool = False,
    ):
        """``axes_only`` builds just what the project axis pass needs
        (aliases, bindings, parents) and skips hazard-context discovery —
        pass 1 of ``lint_paths`` runs over every file, so its cost is the
        serial fraction of a ``--jobs`` scan."""
        self.path = path
        self.source = source
        self.tree = tree
        self.project = project
        self.aliases = _collect_aliases(tree)
        self.step_fns: list[FnCtx] = []
        self.epoch_fns: list[FnCtx] = []
        self.jit_sites: list[JitSite] = []
        #: names bound to jitted callables (``f = jax.jit(...)``,
        #: ``self._train_step = jax.jit(...)``, decorated defs) — DML106's
        #: notion of "this call dispatches device work"
        self.jitted_names: set[str] = set()
        #: names (incl. dotted ``self.f`` chains) bound to jitted callables
        #: with donated args -> set of donated positional indexes (DML204)
        self.donating_names: dict[str, set[int]] = {}
        #: ``shard_map`` call sites (DML202) and the
        #: function defs provably wrapped by one (DML201/DML203 context)
        self.shard_map_calls: list[ast.Call] = []
        self.shard_mapped_defs: set[ast.AST] = set()
        #: child -> parent for every node (scope lookups for the dataflow
        #: rules; built once, O(module size))
        self.parents: dict[ast.AST, ast.AST] = {
            child: parent for parent in ast.walk(tree) for child in ast.iter_child_nodes(parent)
        }
        #: module-scope bindings (dataflow.Bindings); per-function bindings
        #: are computed lazily and cached in _fn_bindings
        self.bindings = dataflow.module_bindings(tree)
        self._fn_bindings: dict[ast.AST, dataflow.Bindings] = {}
        if not axes_only:
            self._collect()
        #: axis names this module provably declares (needs bindings+parents)
        self.declared_axes: set[str] = dataflow.collect_declared_axes(tree, self)

    # -- scopes (dataflow) --------------------------------------------------
    def enclosing_function(self, node: ast.AST) -> ast.AST | None:
        """The nearest enclosing FunctionDef/AsyncFunctionDef, or None."""
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def enclosing_functions(self, node: ast.AST) -> list[ast.AST]:
        """All enclosing function defs, innermost first."""
        out = []
        fn = self.enclosing_function(node)
        while fn is not None:
            out.append(fn)
            fn = self.enclosing_function(fn)
        return out

    def fn_bindings(self, fn: ast.AST) -> "dataflow.Bindings":
        if fn not in self._fn_bindings:
            self._fn_bindings[fn] = dataflow.function_bindings(fn)
        return self._fn_bindings[fn]

    def scopes_at(self, node: ast.AST) -> list["dataflow.Bindings"]:
        """The binding-scope chain at ``node``: enclosing functions
        innermost-first, then the module scope."""
        return [self.fn_bindings(fn) for fn in self.enclosing_functions(node)] + [self.bindings]

    def known_axes(self) -> set[str]:
        """Every mesh axis name considered declared for this module: the
        framework vocabulary, this module's declarations, and (when linting
        a whole tree) every other scanned module's."""
        axes = set(dataflow.BUILTIN_AXES) | self.declared_axes
        if self.project is not None:
            axes |= self.project.declared_axes
        return axes

    # -- name resolution ----------------------------------------------------
    def resolve(self, node: ast.AST) -> str | None:
        """Dotted name of an expression with import aliases expanded
        (``np.random.rand`` -> ``numpy.random.rand``), or None."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(self.aliases.get(node.id, node.id))
            return ".".join(reversed(parts))
        return None

    # -- discovery ----------------------------------------------------------
    def _collect(self) -> None:
        jitted_defs: dict[ast.AST, dict[str, ast.expr]] = {}
        defs_by_name: dict[str, list[ast.AST]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, []).append(node)

        # jit decorators and calls
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    kwargs = self._jit_kwargs(dec)
                    if kwargs is not None:
                        self.jit_sites.append(
                            JitSite(dec, node.name, kwargs, dec.lineno, dec.col_offset)
                        )
                        jitted_defs[node] = kwargs
                        self.jitted_names.add(node.name)
            elif isinstance(node, ast.Call):
                kwargs = self._jit_call_kwargs(node)
                if kwargs is None:
                    continue
                target = None
                if node.args and isinstance(node.args[0], ast.Name):
                    target = node.args[0].id
                self.jit_sites.append(
                    JitSite(node, target, kwargs, node.lineno, node.col_offset)
                )
                if target is not None:
                    for d in defs_by_name.get(target, []):
                        jitted_defs.setdefault(d, kwargs)

        # names bound to jit(...) results: f = jax.jit(...), self.f = jax.jit(...)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                kwargs = self._jit_call_kwargs(node.value)
                if kwargs is None:
                    continue
                donated = _donated_argnums(kwargs)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.jitted_names.add(tgt.id)
                        if donated:
                            self.donating_names[tgt.id] = donated
                    elif isinstance(tgt, ast.Attribute):
                        self.jitted_names.add(tgt.attr)
                        if donated:
                            self.donating_names[".".join(attr_chain(tgt))] = donated

        # calls to a @jit(donate_argnums=...)-decorated def donate too
        for node, kwargs in jitted_defs.items():
            donated = _donated_argnums(kwargs)
            if donated and getattr(node, "name", None):
                self.donating_names.setdefault(node.name, donated)

        # shard_map sites and the defs they wrap
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = self.resolve(node.func) or ""
            last = resolved.split(".")[-1] if resolved else ""
            if not last and isinstance(node.func, ast.Attribute):
                last = node.func.attr
            if last != "shard_map":
                continue
            self.shard_map_calls.append(node)
            if node.args:
                target = node.args[0]
                if isinstance(target, ast.Name):
                    for d in defs_by_name.get(target.id, []):
                        self.shard_mapped_defs.add(d)
                elif isinstance(target, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)):
                    self.shard_mapped_defs.add(target)

        # Stage-class step/epoch methods
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not _is_stage_like(node, self):
                continue
            for item in node.body:
                if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                qual = f"{node.name}.{item.name}"
                if item.name in STEP_METHODS:
                    self.step_fns.append(self._make_step_ctx(item, qual, statics=set()))
                elif item.name in EPOCH_METHODS:
                    self.epoch_fns.append(FnCtx(item, "epoch", qual))

        # jit-marked functions (skip ones already collected as Stage methods)
        seen = {fc.node for fc in self.step_fns}
        for node, kwargs in jitted_defs.items():
            if node in seen:
                continue
            statics = _static_params(node, kwargs)
            self.step_fns.append(
                self._make_step_ctx(node, getattr(node, "name", "<fn>"), statics)
            )

    def _make_step_ctx(self, node, qualname: str, statics: set[str]) -> FnCtx:
        seeds = set()
        for fn in _own_and_nested_defs(node):
            for p in _param_names(fn):
                if p not in ("self", "cls") and p not in statics:
                    seeds.add(p)
        return FnCtx(node, "step", qualname, tainted=_compute_taint(node, seeds))

    def _jit_kwargs(self, dec: ast.AST) -> dict[str, ast.expr] | None:
        """kwargs of a jit decorator (``@jax.jit``, ``@partial(jax.jit, ...)``,
        ``@jax.jit(static_argnames=...)``), else None."""
        if self.resolve(dec) in _JIT_NAMES:
            return {}
        if isinstance(dec, ast.Call):
            return self._jit_call_kwargs(dec)
        return None

    def _jit_call_kwargs(
        self, call: ast.Call, allow_partial: bool = True
    ) -> dict[str, ast.expr] | None:
        """kwargs of a ``jax.jit(...)`` / ``partial(jax.jit, ...)`` call node,
        or None if the call is not jit-like."""
        fname = self.resolve(call.func)
        if fname in _JIT_NAMES:
            return {kw.arg: kw.value for kw in call.keywords if kw.arg}
        if (
            allow_partial
            and fname in _PARTIAL_NAMES
            and call.args
            and self.resolve(call.args[0]) in _JIT_NAMES
        ):
            return {kw.arg: kw.value for kw in call.keywords if kw.arg}
        return None


def _collect_aliases(tree: ast.Module) -> dict[str, str]:
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _is_stage_like(cls: ast.ClassDef, ctx: ModuleCtx) -> bool:
    """A class is stage-like if its own name or any base's terminal segment
    ends with 'Stage' (``dml.TrainValStage``, ``Stage``, ``MyBaseStage``)."""
    if cls.name.endswith("Stage"):
        return True
    for base in cls.bases:
        name = ctx.resolve(base)
        if name and name.split(".")[-1].endswith("Stage"):
            return True
    return False


def _param_names(fn) -> list[str]:
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    if args.kwarg:
        names.append(args.kwarg.arg)
    return names


def _own_and_nested_defs(node) -> Iterator[ast.AST]:
    yield node
    for sub in ast.walk(node):
        if sub is not node and isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield sub


def _static_params(fn, jit_kwargs: dict[str, ast.expr]) -> set[str]:
    """Parameter names excluded from tracing by static_argnums/argnames.
    Branching on those is *not* a retrace hazard beyond the (intentional)
    static-arg mechanism itself."""
    statics: set[str] = set()
    names = _param_names(fn)
    kw = jit_kwargs.get("static_argnames")
    if kw is not None:
        for c in ast.walk(kw):
            if isinstance(c, ast.Constant) and isinstance(c.value, str):
                statics.add(c.value)
    kw = jit_kwargs.get("static_argnums")
    if kw is not None:
        for c in ast.walk(kw):
            if isinstance(c, ast.Constant) and isinstance(c.value, int):
                if 0 <= c.value < len(names):
                    statics.add(names[c.value])
    return statics


def _donated_argnums(jit_kwargs: dict[str, ast.expr]) -> set[int]:
    """Positional indexes a jit call donates (``donate_argnums`` int/tuple
    literals). ``donate_argnames`` cannot be mapped to positions without the
    signature, so it contributes nothing here — DML204 stays silent rather
    than mis-attributing a donation."""
    donated: set[int] = set()
    kw = jit_kwargs.get("donate_argnums")
    if kw is not None:
        for c in ast.walk(kw):
            if isinstance(c, ast.Constant) and isinstance(c.value, int):
                donated.add(c.value)
    return donated


def _compute_taint(fn, seeds: set[str]) -> set[str]:
    """Forward taint: ``seeds`` plus every name assigned from an expression
    referencing a tainted name, to a fixpoint. Coarse by design — the rules
    that consume it (DML104) additionally prune statically-safe accesses
    (``.shape``, ``isinstance``, ``is None``...)."""
    tainted = set(seeds)
    for _ in range(10):  # fixpoint cap; real functions converge in 1-2 passes
        changed = False
        for node in ast.walk(fn):
            value = None
            targets: list[ast.AST] = []
            if isinstance(node, ast.Assign):
                value, targets = node.value, node.targets
            elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
                value, targets = node.value, [node.target]
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                value, targets = node.iter, [node.target]
            elif isinstance(node, ast.withitem) and node.optional_vars is not None:
                value, targets = node.context_expr, [node.optional_vars]
            if value is None or not expr_tainted(value, tainted):
                continue
            for tgt in targets:
                for n in ast.walk(tgt):
                    if isinstance(n, ast.Name) and n.id not in tainted:
                        tainted.add(n.id)
                        changed = True
        if not changed:
            break
    return tainted


def expr_tainted(expr: ast.AST, tainted: set[str]) -> bool:
    """Whether any Name in the expression subtree is tainted."""
    return any(
        isinstance(n, ast.Name) and n.id in tainted for n in ast.walk(expr)
    )


# ------------------------------------------------- sanctioned-sync detection


def _is_measure_call(expr: ast.AST) -> bool:
    """``<anything>.measure(...)`` — a StallTimer-accounted block."""
    return (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Attribute)
        and expr.func.attr == "measure"
    )


def attr_chain(node: ast.AST) -> list[str]:
    """['self', '_stall', 'fetch'] for ``self._stall.fetch``."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return list(reversed(parts))


def is_stall_accounted(call: ast.Call) -> bool:
    """``fetch``/``block`` on a stall-timer receiver: the framework's
    sanctioned, *accounted* host block (utils.profiling.StallTimer)."""
    if not isinstance(call.func, ast.Attribute):
        return False
    if call.func.attr not in ("fetch", "block", "measure"):
        return False
    return any("stall" in seg.lower() for seg in attr_chain(call.func)[:-1])


def walk_fn(fn_node) -> Iterator[tuple[ast.AST, bool]]:
    """Yield ``(descendant, in_measure)`` for every node under ``fn_node``,
    where ``in_measure`` is True inside a ``with <x>.measure():`` body."""

    def rec(node: ast.AST, in_measure: bool) -> Iterator[tuple[ast.AST, bool]]:
        for child in ast.iter_child_nodes(node):
            yield child, in_measure
            if isinstance(child, ast.With) and any(
                _is_measure_call(i.context_expr) for i in child.items
            ):
                for item in child.items:
                    yield from rec(item, in_measure)
                for stmt in child.body:
                    yield stmt, True
                    yield from rec(stmt, True)
            else:
                yield from rec(child, in_measure)

    yield from rec(fn_node, False)


# -------------------------------------------------------------- entry points


def lint_source(
    source: str,
    path: str = "<string>",
    select: Iterable[str] | None = None,
    ignore: Iterable[str] | None = None,
    project: "dataflow.ProjectContext | None" = None,
) -> list[Finding]:
    """Lint one module's source. Returns findings sorted by location, with
    suppression comments already applied. ``select``/``ignore`` accept exact
    rule ids and ``DML2xx`` family wildcards."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [
            Finding(
                PARSE_ERROR_RULE,
                path,
                int(e.lineno or 1),
                int(e.offset or 0),
                f"could not parse file: {e.msg}",
            )
        ]
    ctx = ModuleCtx(path, source, tree, project=project)
    sup = Suppressions.parse(source)
    return _run_module_rules(ctx, sup, select, ignore)


def _run_module_rules(
    ctx: ModuleCtx,
    sup: Suppressions,
    select: Iterable[str] | None,
    ignore: Iterable[str] | None,
) -> list[Finding]:
    """Run the per-module RULES over one context, suppressions applied."""
    selected = set(expand_rule_ids(select)[0]) if select else set(RULES)
    ignored = set(expand_rule_ids(ignore)[0]) if ignore else set()
    out: set[Finding] = set()
    for info in RULES.values():
        if info.id not in selected or info.id in ignored:
            continue
        for f in info.check(ctx):
            if not sup.is_suppressed(f):
                out.add(f)
    return sorted(out, key=Finding.sort_key)


def lint_file(
    path: str | os.PathLike,
    select: Iterable[str] | None = None,
    ignore: Iterable[str] | None = None,
    project: "dataflow.ProjectContext | None" = None,
) -> list[Finding]:
    path = os.fspath(path)
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
    except OSError as e:
        return [Finding(PARSE_ERROR_RULE, path, 1, 0, f"could not read file: {e}")]
    return lint_source(source, path=path, select=select, ignore=ignore, project=project)


_SKIP_DIRS = frozenset({"__pycache__", ".git", ".hg", ".venv", "venv", "node_modules", "build", "dist", ".eggs"})


def iter_python_files(paths: Iterable[str | os.PathLike]) -> Iterator[str]:
    """Expand files/directories into a sorted stream of ``*.py`` paths."""
    for p in paths:
        p = os.fspath(p)
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs if d not in _SKIP_DIRS and not d.startswith("."))
                for fname in sorted(files):
                    if fname.endswith(".py"):
                        yield os.path.join(root, fname)
        else:
            yield p


def build_project_context(files: Iterable[str | os.PathLike]) -> "dataflow.ProjectContext":
    """Pass 1 of a multi-file lint: parse every file and union its declared
    mesh axes into one :class:`dataflow.ProjectContext`. Unreadable or
    unparseable files contribute nothing here — pass 2 reports them."""
    project = dataflow.ProjectContext()
    for fpath in files:
        try:
            with open(os.fspath(fpath), "r", encoding="utf-8", errors="replace") as f:
                source = f.read()
            tree = ast.parse(source)
        except (OSError, SyntaxError):
            continue
        ctx = ModuleCtx(os.fspath(fpath), source, tree, axes_only=True)
        project.merge_module(ctx.declared_axes)
    return project


_EMPTY_SUP = {"by_line": {}, "file_wide": []}


def _sup_to_data(sup: Suppressions) -> dict:
    """JSON form of a Suppressions (the incremental cache persists it so
    the project pass can honor directives in files it never re-parses)."""
    return {
        "by_line": {str(k): sorted(v) for k, v in sup.by_line.items()},
        "file_wide": sorted(sup.file_wide),
    }


def _sup_from_data(data: dict | None) -> Suppressions:
    sup = Suppressions()
    if data:
        sup.by_line = {int(k): set(v) for k, v in data.get("by_line", {}).items()}
        sup.file_wide = set(data.get("file_wide", ()))
    return sup


def _error_result(path: str, finding: Finding) -> dict:
    return {"path": path, "findings": [finding], "summary": None, "axes": [], "sup": _EMPTY_SUP}


def _module_result(
    ctx: ModuleCtx,
    select: Iterable[str] | None,
    ignore: Iterable[str] | None,
    want_summary: bool,
) -> dict:
    """Per-file analysis product: module-rule findings, the (optional)
    call-graph summary, declared axes, and serialized suppressions — one
    parse feeds all four (pass 1 and pass 2 share the ModuleCtx)."""
    sup = Suppressions.parse(ctx.source)
    findings = _run_module_rules(ctx, sup, select, ignore)
    summary = None
    if want_summary:
        from .callgraph import summarize_module

        summary = summarize_module(ctx)
    return {
        "path": ctx.path,
        "findings": findings,
        "summary": summary,
        "axes": sorted(ctx.declared_axes),
        "sup": _sup_to_data(sup),
    }


def _analyze_file(
    path: str | os.PathLike,
    select: Iterable[str] | None,
    ignore: Iterable[str] | None,
    project: "dataflow.ProjectContext",
    want_summary: bool,
) -> dict:
    path = os.fspath(path)
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
    except OSError as e:
        return _error_result(path, Finding(PARSE_ERROR_RULE, path, 1, 0, f"could not read file: {e}"))
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return _error_result(
            path,
            Finding(
                PARSE_ERROR_RULE,
                path,
                int(e.lineno or 1),
                int(e.offset or 0),
                f"could not parse file: {e.msg}",
            ),
        )
    ctx = ModuleCtx(path, source, tree, project=project)
    return _module_result(ctx, select, ignore, want_summary)


#: per-worker state installed once by the pool initializer — the pass-1
#: axis registry and the run config are shared via fork/initargs instead
#: of being rebuilt (or re-shipped) for every task
_WORKER_STATE: dict = {}


def _pool_init(select, ignore, axes, want_summary) -> None:
    from . import lifecycle, rules, rules_concurrency, rules_data, rules_perf, rules_sharding  # noqa: F401 — register rules

    _WORKER_STATE["select"] = select
    _WORKER_STATE["ignore"] = ignore
    _WORKER_STATE["project"] = dataflow.ProjectContext(declared_axes=set(axes))
    _WORKER_STATE["want_summary"] = want_summary


def _analyze_task(path: str) -> dict:
    st = _WORKER_STATE
    return _analyze_file(path, st["select"], st["ignore"], st["project"], st["want_summary"])


def lint_paths(
    paths: Iterable[str | os.PathLike],
    select: Iterable[str] | None = None,
    ignore: Iterable[str] | None = None,
    jobs: int = 1,
    project: "dataflow.ProjectContext | None" = None,
    callgraph: bool = True,
    cache: str | os.PathLike | None = None,
    stats: dict | None = None,
    ir: bool = False,
    git_state: "tuple[str, frozenset[str]] | None" = None,
) -> list[Finding]:
    """Lint files and/or directories (recursive); returns sorted findings.

    Two passes share one parse per file: pass 1 runs the per-module RULES
    and extracts a call-graph summary; pass 2 folds every summary into a
    :class:`~dmlcloud_tpu.lint.callgraph.ProjectGraph` and runs the
    interprocedural PROJECT_RULES (DML5xx) over it — disable with
    ``callgraph=False`` to fall back to the module-local rules only.

    ``cache`` names an incremental cache file (lint/cache.py): unchanged
    files reuse their cached findings/summaries; a changed file re-lints
    itself plus its transitive reverse importers. ``stats`` (a dict, filled
    in place) reports ``files``/``linted``/``reused`` for callers that need
    to see the plan.

    ``jobs > 1`` fans the per-file pass out over a ``ProcessPoolExecutor``
    whose initializer installs the shared pass-1 registries once per
    worker; on a single-core host the pool is a pure loss, so ``jobs``
    silently collapses to 1 there. Findings
    merge in path order either way, so output is deterministic.

    ``ir=True`` adds the DML6xx IR pass (lint/ir.py — the ONE jax-needing
    pass): files defining a ``dml_verify_programs()`` hook get their
    programs traced/compiled on CPU and audited, findings merging into
    the same stream (and the same cache entries — a warm ``--ir`` run
    replays them byte-identically without importing jax)."""
    files = list(iter_python_files(paths))
    if jobs > 1 and (os.cpu_count() or 1) == 1:
        jobs = 1

    want_summary = callgraph or cache is not None
    cache_obj = None
    reused: dict[str, dict] = {}
    to_lint: list[str] = list(files)
    if cache is not None:
        from .cache import LintCache

        cache_obj = LintCache(cache, select=select, ignore=ignore, ir=ir,
                              git_state=git_state)
        to_lint, reused = cache_obj.plan(files)

    if project is None:
        project = dataflow.ProjectContext()
    for entry in reused.values():
        project.merge_module(set(entry.get("axes", ())))

    results: list[dict] = []
    if jobs > 1 and len(to_lint) > 1:
        from concurrent.futures import ProcessPoolExecutor

        # the axis registry must be complete before any worker lints, so
        # the (cheap, axes-only) discovery pass stays in the parent
        project.merge_module(build_project_context(to_lint).declared_axes)
        initargs = (
            tuple(select) if select else None,
            tuple(ignore) if ignore else None,
            frozenset(project.declared_axes),
            want_summary,
        )
        with ProcessPoolExecutor(max_workers=jobs, initializer=_pool_init, initargs=initargs) as pool:
            results.extend(pool.map(_analyze_task, to_lint))
    else:
        # serial path parses once: contexts are built (and their axes
        # merged) first, rules run after the registry is complete
        pending: list[ModuleCtx] = []
        for fpath in to_lint:
            fpath = os.fspath(fpath)
            try:
                with open(fpath, "r", encoding="utf-8", errors="replace") as f:
                    source = f.read()
            except OSError as e:
                results.append(
                    _error_result(fpath, Finding(PARSE_ERROR_RULE, fpath, 1, 0, f"could not read file: {e}"))
                )
                continue
            try:
                tree = ast.parse(source)
            except SyntaxError as e:
                results.append(
                    _error_result(
                        fpath,
                        Finding(
                            PARSE_ERROR_RULE,
                            fpath,
                            int(e.lineno or 1),
                            int(e.offset or 0),
                            f"could not parse file: {e.msg}",
                        ),
                    )
                )
                continue
            ctx = ModuleCtx(fpath, source, tree, project=project)
            project.merge_module(ctx.declared_axes)
            pending.append(ctx)
        for ctx in pending:
            results.append(_module_result(ctx, select, ignore, want_summary))

    if ir:
        # the IR pass runs serially in the parent (it imports jax and
        # compiles; a process pool would re-pay jax startup per worker) and
        # merges into each hook file's result BEFORE the cache stores it —
        # a warm run replays these findings without touching jax at all
        from . import ir as ir_mod

        for r in results:
            if not ir_mod.has_hook(r["path"]):
                continue
            ir_findings = ir_mod.verify_file(r["path"], select=select, ignore=ignore)
            if ir_findings:
                r["findings"] = sorted(
                    set(r["findings"]) | set(ir_findings), key=Finding.sort_key
                )

    findings: list[Finding] = []
    for entry in reused.values():
        findings.extend(Finding(**d) for d in entry.get("findings", ()))
    for r in results:
        findings.extend(r["findings"])

    if callgraph:
        from . import lifecycle  # noqa: F401 — register the DML5xx rules
        from .callgraph import ProjectGraph

        summaries = [r["summary"] for r in results if r.get("summary")]
        summaries += [e["summary"] for e in reused.values() if e.get("summary")]
        graph = ProjectGraph(summaries)
        sups = {r["path"]: _sup_from_data(r.get("sup")) for r in results}
        for p, e in reused.items():
            sups[p] = _sup_from_data(e.get("sup"))
        selected = set(expand_rule_ids(select)[0]) if select else set(RULES) | set(PROJECT_RULES)
        ignored = set(expand_rule_ids(ignore)[0]) if ignore else set()
        for info in PROJECT_RULES.values():
            if info.id not in selected or info.id in ignored:
                continue
            for f in info.check(graph):
                sup = sups.get(f.path)
                if sup is None or not sup.is_suppressed(f):
                    findings.append(f)

    if cache_obj is not None:
        cache_obj.store(results, reused)

    if stats is not None:
        stats["files"] = len(files)
        stats["linted"] = sorted(os.fspath(p) for p in to_lint)
        stats["reused"] = sorted(reused)

    return sorted(set(findings), key=Finding.sort_key)
