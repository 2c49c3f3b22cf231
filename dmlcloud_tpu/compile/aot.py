"""Ahead-of-time compilation of jitted step functions.

The jit-on-first-call model puts the whole XLA compile bill inside step 1 of
the data loop — an unbounded, unannounced stall, and the place where a
sharding/shape mistake surfaces after minutes of setup. The MaxText/levanter
answer is to compile *before* the loop against abstract inputs::

    lowered = jitted_fn.lower(state_spec, batch_spec)   # trace only
    compiled = lowered.compile()                        # XLA (or cache hit)

``PrecompiledStep`` wraps one jitted step function in a registry of such
compiled executables keyed by the *call signature* (pytree structure +
per-leaf shape/dtype/sharding):

- ``precompile(*specs)`` compiles one signature ahead of time (timed, and
  accounted against the persistent cache as a hit or miss);
- calling it routes a matching signature straight to its compiled
  executable (no retrace, no dispatch-path cache probe of unknown cost) and
  falls back to the plain jitted function for anything else, counting each
  *new* unseen signature once — the ``misc/recompiles`` metric;
- ``_cache_size()`` reports distinct signatures seen, which is exactly the
  probe ``lint.TraceGuard`` reads, so the runtime retrace guard works
  unchanged on top.

Abstract specs come from ``abstract_spec`` (any concrete or abstract pytree
-> ``ShapeDtypeStruct`` skeleton) and ``global_batch_spec`` (the sharded
layout ``make_global_batch`` will produce for a host batch on a mesh).
``validate_global_batch_spec`` moves the classic step-1 crash — a batch dim
the mesh cannot divide — to stage start.

Quantized-training states precompile unchanged: the int8 step's params stay
a plain fp32 tree (the ``QuantTrainTensor`` wrap happens INSIDE the traced
loss closure, stage.py) and the delayed amax tree in
``extras[models.quant.QUANT_AMAX_KEY]`` is ordinary array leaves, so the
signature — and therefore the AOT cache key and the TraceGuard budget —
is exactly the full-precision one.
"""

from __future__ import annotations

import os
import time
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import mesh as mesh_lib
from . import cache as cache_lib

__all__ = [
    "PrecompiledStep",
    "abstract_spec",
    "global_batch_spec",
    "signature_of",
    "validate_global_batch_spec",
]


def abstract_spec(tree: Any) -> Any:
    """``ShapeDtypeStruct`` skeleton of a pytree: concrete jax.Arrays keep
    their sharding, host arrays/scalars contribute shape+dtype only, and
    existing ``ShapeDtypeStruct`` leaves pass through."""

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        arr = x if hasattr(x, "shape") and hasattr(x, "dtype") else np.asarray(x)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    return jax.tree_util.tree_map(leaf, tree)


def global_batch_spec(batch_or_spec: Any, mesh: Mesh, pspec: P | None = None) -> Any:
    """The abstract layout ``make_global_batch`` produces for a host batch:
    every leaf carries the mesh's batch sharding. Accepts a concrete batch
    or an ``abstract_spec``-style skeleton."""
    if pspec is None:
        pspec = mesh_lib.batch_pspec(mesh)
    sharding = NamedSharding(mesh, pspec)
    spec = abstract_spec(batch_or_spec)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), spec
    )


def validate_global_batch_spec(spec: Any, mesh: Mesh, pspec: P | None = None) -> None:
    """Raise the step-1 sharding crash at stage start instead: every leaf's
    leading (batch) dim must divide over the mesh's data-parallel axes."""
    dp = mesh_lib.data_parallel_size(mesh)
    if dp <= 1:
        return
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract_spec(spec))[0]:
        shape = leaf.shape
        if len(shape) >= 1 and shape[0] % dp != 0:
            raise ValueError(
                f"batch leaf {mesh_lib.path_str(path) or '<root>'} has leading dim "
                f"{shape[0]}, not divisible by the mesh's data-parallel size {dp} "
                f"(axes {mesh_lib.data_axes(mesh)}); this would crash at step 1 — fix "
                "the batch size, the bucket set, or the mesh"
            )


def _leaf_signature(x: Any) -> tuple:
    shape = tuple(getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", type(x).__name__))
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        sharding = None  # single-device/unspecified: match on shape+dtype only
    return (shape, dtype, sharding)


def signature_of(args: tuple) -> tuple:
    """Hashable call signature: pytree structure + per-leaf
    shape/dtype/(named) sharding. Two calls with equal signatures reuse the
    same compiled executable."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_leaf_signature(x) for x in leaves))


class PrecompiledStep:
    """Signature-keyed registry of AOT-compiled executables over one jitted
    function (see module docstring). Thread-compatible with the single
    training thread; not locked."""

    def __init__(self, fn: Any, name: str = "step"):
        if not hasattr(fn, "lower"):
            raise TypeError(
                f"PrecompiledStep needs a jitted function (got {type(fn).__name__}); "
                "wrap the fn with jax.jit first"
            )
        self._fn = fn
        self.name = name
        self._compiled: dict[tuple, Any] = {}
        self._seen: set[tuple] = set()
        self._recompiles = 0

    def precompile(self, *abstract_args: Any) -> float:
        """Lower + compile one signature ahead of the data loop; returns the
        wall-clock ms this compilation took (0.0 if already registered).
        Accounts a persistent-cache hit when the compile added no new cache
        entry (the executable was deserialized, not built). With a journal
        armed, the ``compile`` span carries ``lower_s`` (the ``.lower()``
        part) and ``cache_hit``, and the step's phase map is written after
        it, timed as the ``phase_map`` set-up span."""
        sig = signature_of(abstract_args)
        if sig in self._compiled:
            return 0.0
        from ..telemetry import journal as _journal

        entries_before = cache_lib.entry_count()
        t0 = time.perf_counter()
        lowered = self._fn.lower(*abstract_args)
        t_lowered = time.perf_counter()
        compiled = lowered.compile()
        t1 = time.perf_counter()
        elapsed_ms = (t1 - t0) * 1e3
        entries_after = cache_lib.entry_count()
        hit = (
            entries_before is not None
            and entries_after is not None
            and entries_after == entries_before
        )
        cache_lib.record_compile(hit=hit, elapsed_ms=elapsed_ms)
        j = _journal.active_journal()
        if j is not None:
            # with a journal armed, and only then, the step's phase map goes beside
            # it: what joins a profile's operations to the program's phases
            from ..utils.profiling import write_phase_map

            n = len(self._compiled) + 1
            safe = "".join(c if c.isalnum() or c in "._-" else "_" for c in self.name)
            path = os.path.join(j.directory, f"phases-{safe}-{n}.json")
            t_map = time.perf_counter()
            phases = write_phase_map(compiled, path, self.name)
            _journal.setup("phase_map", t_map)
            j.emit("compile", t0, t1, label=self.name, signature=n, phases=phases,
                   lower_s=round(t_lowered - t0, 9), cache_hit=hit)
        self._compiled[sig] = compiled
        self._seen.add(sig)
        return elapsed_ms

    def __call__(self, *args: Any):
        sig = signature_of(args)
        compiled = self._compiled.get(sig)
        if compiled is not None:
            return compiled(*args)
        if sig not in self._seen:
            self._seen.add(sig)
            self._recompiles += 1
        return self._fn(*args)  # jit path: compiles (or cache-hits) on its own

    # -- introspection ------------------------------------------------------
    def any_compiled(self) -> Any:
        """One AOT-compiled executable (arbitrary signature), or None —
        enough for per-step cost analysis (telemetry/goodput.py), which is
        signature-independent to first order."""
        return next(iter(self._compiled.values()), None)

    @property
    def signatures(self) -> int:
        """Distinct signatures precompiled (the bounded set buckets target)."""
        return len(self._compiled)

    @property
    def recompiles(self) -> int:
        """Signatures that arrived at call time without a precompiled
        executable (counted once each) since the last ``pop_recompiles``."""
        return self._recompiles

    def pop_recompiles(self) -> int:
        n = self._recompiles
        self._recompiles = 0
        return n

    def _cache_size(self) -> int:
        """Distinct signatures seen (precompiled + fallback) — the probe
        ``lint.TraceGuard`` reads across calls."""
        return len(self._seen)
