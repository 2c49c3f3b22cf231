"""Mesh & sharding core — the TPU-native replacement for DDP.

The reference's only parallelism is data-parallel DDP wrapping
(/root/reference/dmlcloud/pipeline.py:72-74) with NCCL bucketed allreduce.
Here the first-class object is a ``jax.sharding.Mesh`` over the device grid:
the batch is sharded over the ``data`` (and ``fsdp``) axes, parameters are
placed by a sharding *policy* (replicated == DDP; ``fsdp`` == ZeRO-3; explicit
rules == tensor parallelism), and the gradient allreduce is emitted by XLA as
a fused psum over ICI inside the compiled step — no hook machinery.

Axes are named, and every higher layer speaks these names:

- ``data``  — pure data parallelism (batch sharding)
- ``fsdp``  — parameter-sharded data parallelism (batch + params sharded)
- ``model`` — tensor parallelism (attention heads / mlp hidden)
- ``seq``   — sequence/context parallelism (ring attention, ops/ring_attention.py)
- ``expert``— expert parallelism for MoE layers
- ``pipe``  — pipeline parallelism stages

A single-axis ``data`` mesh over all devices reproduces the reference's DDP
semantics exactly (replicated params, batch split, mean-reduced grads).
"""

from __future__ import annotations

import logging
import math
import re
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_logger = logging.getLogger("dmlcloud_tpu")

DATA, FSDP, MODEL, SEQ, EXPERT, PIPE = "data", "fsdp", "model", "seq", "expert", "pipe"

#: rule list: (regex over '/'-joined param path, PartitionSpec)
PartitionRules = Sequence[tuple[str, P]]


def parse_mesh_axes(spec: str) -> dict[str, int]:
    """Parse a CLI mesh spec like ``'data=2,fsdp=4'`` into an axes dict for
    :func:`create_mesh` / ``TrainingPipeline.set_mesh`` (``-1`` absorbs the
    remaining devices). One shared parser so every example/CLI rejects a
    malformed spec with the same actionable error."""
    axes: dict[str, int] = {}
    for part in spec.split(","):
        name, eq, size = part.partition("=")
        name = name.strip()
        try:
            if not (name and eq):
                raise ValueError
            parsed = int(size)
        except ValueError:
            raise ValueError(
                f"malformed mesh spec {spec!r}: expected comma-separated name=int "
                f"pairs like 'data=2,fsdp=4' (bad part: {part!r})"
            ) from None
        if name in axes:
            # a duplicate would silently drop the first size (dict overwrite)
            # — e.g. 'data=2,data=4' becoming {'data': 4}
            raise ValueError(f"malformed mesh spec {spec!r}: axis {name!r} given more than once")
        axes[name] = parsed
    return axes


def create_mesh(
    axes: Mapping[str, int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh.

    ``axes`` maps axis name -> size; one axis may be ``-1`` to absorb all
    remaining devices. Default: ``{'data': -1}`` — the DDP-equivalent mesh.
    Uses ``mesh_utils.create_device_mesh`` when the shape matches the full
    device count so the ICI topology is respected.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if axes is None:
        axes = {DATA: -1}
    names = list(axes.keys())
    sizes = list(axes.values())
    n = len(devices)
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes product {known}")
        sizes[sizes.index(-1)] = n // known
    if math.prod(sizes) != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {math.prod(sizes)} devices, have {n}")
    try:
        from jax.experimental import mesh_utils

        grid = mesh_utils.create_device_mesh(tuple(sizes), devices=devices)
    except Exception:
        grid = np.array(devices).reshape(tuple(sizes))
    return Mesh(grid, tuple(names))


def auto_mesh(
    n_devices: int | None = None,
    axis_names: Sequence[str] = (DATA, FSDP, MODEL),
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Factorize ``n_devices`` over ``axis_names`` (greedy powers of two,
    leading axes get the larger factors) — used by dry-runs and quick starts."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    sizes = [1] * len(axis_names)
    rem, i = n, 0
    # round-robin factor assignment: split off smallest prime factors one at a time
    while rem > 1:
        for p in (2, 3, 5, 7, 11, 13):
            if rem % p == 0:
                sizes[i % len(sizes)] *= p
                rem //= p
                break
        else:
            sizes[i % len(sizes)] *= rem
            rem = 1
        i += 1
    return create_mesh(dict(zip(axis_names, sizes)), devices=devices)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """The axes the batch dimension is sharded over: ``data`` plus ``fsdp``
    when present (standard FSDP batch layout)."""
    return tuple(a for a in (DATA, FSDP) if a in mesh.axis_names)


def batch_pspec(mesh: Mesh) -> P:
    ax = data_axes(mesh)
    return P(ax if len(ax) > 1 else (ax[0] if ax else None))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(mesh))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_parallel_size(mesh: Mesh) -> int:
    return int(math.prod(mesh.shape[a] for a in data_axes(mesh)) or 1)


def respec_for_mesh(spec: P | Sequence, shape: Sequence[int], mesh: Mesh) -> P:
    """Re-target a PartitionSpec recorded on ONE mesh onto ``mesh`` — the
    elastic-resume primitive: a checkpoint saved on an N-device mesh carries
    each leaf's spec, and the resumed run rebuilds shardings for whatever
    mesh it actually got. Axes the new mesh lacks are dropped (replicated);
    axes that no longer divide their dim (the axis grew, e.g. fsdp 2 -> 8 on
    a dim of 4) are relocated to another divisible dim when one exists, else
    dropped with a warning. Always returns a spec valid on ``mesh``."""
    entries = list(spec) if spec is not None else []
    shape = tuple(shape)
    cleaned: list = [None] * len(shape)
    displaced: list = []
    for i, a in enumerate(entries[: len(shape)]):
        axes = (a,) if isinstance(a, str) else (a or ())
        if a is None or not axes or not all(x in mesh.axis_names for x in axes):
            continue
        n = math.prod(mesh.shape[x] for x in axes)
        if shape[i] % n == 0:
            cleaned[i] = a
        else:
            displaced.append((a, n))
    for a, n in displaced:
        for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
            if cleaned[i] is None and shape[i] % n == 0 and shape[i] >= 2 * n:
                cleaned[i] = a
                break
        else:
            _logger.warning(
                "restore respec: no dim of shape %s divisible by saved axis %r "
                "(size %d on the new mesh); restoring that axis replicated",
                shape, a, n,
            )
    return P(*cleaned)


def spec_to_jsonable(spec: P | None) -> list:
    """A PartitionSpec as a JSON-serialisable list (None | str | [str, ...]
    per dim) — the sharding-sidecar wire format (checkpoint.py)."""
    out: list = []
    for a in (spec or ()):
        if a is None or isinstance(a, str):
            out.append(a)
        else:
            out.append(list(a))
    return out


def spec_from_jsonable(entries: Sequence) -> P:
    """Inverse of :func:`spec_to_jsonable`."""
    return P(*[tuple(a) if isinstance(a, list) else a for a in (entries or ())])


# ---------------------------------------------------------------------------
# parameter sharding policies
# ---------------------------------------------------------------------------

def path_str(path) -> str:
    """'/'-joined pytree key path (dict keys, attr names, sequence indices)
    — the string that sharding rules, LoRA matchers, and quantization
    matchers all run their regexes against."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _fsdp_spec(x: Any, mesh: Mesh, axis: str = FSDP, min_size: int = 2**14) -> P:
    """Shard the largest divisible dim of ``x`` over the fsdp axis; tiny or
    indivisible params stay replicated (they cost nothing)."""
    shape = getattr(x, "shape", ())
    size = int(np.prod(shape)) if shape else 0
    n = mesh.shape.get(axis, 1)
    if n <= 1 or size < min_size:
        return P()
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if shape[i] % n == 0:
            spec = [None] * len(shape)
            spec[i] = axis
            return P(*spec)
    return P()


def make_param_policy(policy: str | PartitionRules | Callable[[str, Any], P]) -> Callable[[str, Any, Mesh], P]:
    """Normalise a sharding policy to ``(path, leaf, mesh) -> PartitionSpec``.

    - ``'replicate'``: every param replicated (DDP semantics).
    - ``'fsdp'``: largest divisible dim sharded over the ``fsdp`` axis (ZeRO-3).
    - rule list ``[(regex, PartitionSpec), ...]``: first match wins, falling
      back to fsdp-or-replicate for unmatched params (T5X-style rules — this
      is how tensor parallelism is expressed).
    - callable ``(path, leaf) -> PartitionSpec``.
    """
    if callable(policy):
        return lambda path, leaf, mesh: policy(path, leaf)
    if policy == "replicate":
        return lambda path, leaf, mesh: P()
    if policy == "fsdp":
        return lambda path, leaf, mesh: _fsdp_spec(leaf, mesh)
    if isinstance(policy, (list, tuple)):
        rules = [(re.compile(pat), spec) for pat, spec in policy]

        def apply_rules(path: str, leaf: Any, mesh: Mesh) -> P:
            for pat, spec in rules:
                if pat.search(path):
                    # Drop axes the mesh doesn't have (lets one rule set serve
                    # many meshes). Axes that don't divide their param dim get
                    # relocated to another divisible dim if one exists (e.g. a
                    # 30522-row word table on fsdp=4 moves the fsdp shards to
                    # the hidden dim), else dropped with a warning — the rule
                    # must also cover e.g. a 2-row type table without crashing.
                    shape = getattr(leaf, "shape", ())
                    cleaned: list = []
                    displaced: list = []
                    for i, a in enumerate(spec):
                        axes = (a,) if isinstance(a, str) else a
                        if a is None or not all(x in mesh.axis_names for x in axes):
                            cleaned.append(None)
                            continue
                        n = math.prod(mesh.shape[x] for x in axes)
                        if i < len(shape) and shape[i] % n == 0:
                            cleaned.append(a)
                        else:
                            cleaned.append(None)
                            displaced.append((a, n))
                    if displaced:
                        cleaned += [None] * (len(shape) - len(cleaned))
                    for a, n in displaced:
                        for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
                            if cleaned[i] is None and shape[i] % n == 0 and shape[i] >= 2 * n:
                                cleaned[i] = a
                                _logger.info(
                                    "param %s: axis %r (size %d) does not divide its rule dim; "
                                    "relocated to dim %d of shape %s",
                                    path, a, n, i, tuple(shape),
                                )
                                break
                        else:
                            _logger.warning(
                                "param %s: no dim of shape %s divisible by axis %r "
                                "(size %d); leaving that axis unsharded (replicated)",
                                path, tuple(shape), a, n,
                            )
                    return P(*cleaned)
            return _fsdp_spec(leaf, mesh) if FSDP in mesh.axis_names else P()

        return apply_rules
    raise ValueError(f"unknown sharding policy: {policy!r}")


def sharding_for(tree: Any, mesh: Mesh, policy: str | PartitionRules | Callable = "replicate") -> Any:
    """A pytree of NamedShardings matching ``tree`` under ``policy`` — feed to
    ``jax.jit(in_shardings=...)`` or ``jax.device_put``."""
    fn = make_param_policy(policy)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, fn(path_str(path), leaf, mesh)), tree
    )


def shard_pytree(tree: Any, mesh: Mesh, policy: str | PartitionRules | Callable = "replicate") -> Any:
    """Place ``tree`` on the mesh under ``policy`` (the moment the reference
    wraps with DDP, pipeline.py:72-74, we instead lay params out on the mesh)."""
    return jax.device_put(tree, sharding_for(tree, mesh, policy))


def make_global_batch(batch: Any, mesh: Mesh, pspec: P | None = None) -> Any:
    """Form a globally-sharded jax.Array from per-process host data.

    Single-process: a plain sharded ``device_put``. Multi-process:
    ``jax.make_array_from_process_local_data`` stitches each host's shard into
    one global array — the moment the reference relied on DistributedSampler
    to keep per-rank batches disjoint, we instead declare the global batch.
    """
    if pspec is None:
        pspec = batch_pspec(mesh)
    sharding = NamedSharding(mesh, pspec)

    def put(x):
        if isinstance(x, jax.Array):
            if x.sharding == sharding:
                return x  # already laid out — pass through
            if not x.is_fully_addressable:
                return x  # already a global array (e.g. from device_iterator)
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, np.asarray(x))
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(put, batch)
