"""Checkpoint-directory management + sharded tensor state via Orbax.

Capability parity with /root/reference/dmlcloud/checkpoint.py: collision-free
run-directory naming ``{name}-{YYYY.MM.DD-HH.MM}-{id}`` (:16-34), Slurm-requeue
rediscovery by job id (:37-48), and the directory contract — indicator file,
``config.yaml``, ``log.txt``, ``.slurm-jobid`` (:56-117).

It then closes the reference's honest gap: the reference never serialises
model/optimizer state (only config + logs; SURVEY.md §3.5). Here
``CheckpointDir.state_manager`` exposes an Orbax ``CheckpointManager`` rooted
at ``<dir>/state`` — async, sharded (every host writes its own shards; a
multi-host TPU pod checkpoints in parallel), GCS-path capable, with retention.
The directory-contract files stay root-only; tensor state saves are
collective.
"""

from __future__ import annotations

import logging
import os
import random
import string
import time
from datetime import datetime
from pathlib import Path
from typing import Any

from etils import epath

from .utils import slurm
from .utils.config import Config, as_config

_logger = logging.getLogger("dmlcloud_tpu")


def as_run_path(path: Any) -> epath.Path:
    """Normalise to an ``etils.epath.Path``. URI paths (``gs://``, ``s3://``,
    ...) pass through untouched — ``Path.resolve()`` would mangle the scheme
    into ``gs:/bucket`` before any backend saw it; local paths are expanded
    and absolutised for stable equality across processes."""
    if isinstance(path, epath.Path):
        return path
    s = os.fspath(path)
    if "://" in s:
        return epath.Path(s)
    return epath.Path(os.path.abspath(os.path.expanduser(s)))


def is_remote_path(path: Any) -> bool:
    return "://" in os.fspath(path)


def _normalize_opt(v: Any, _seen: frozenset = frozenset()) -> Any:
    """Structural key for an Orbax option value, comparable across calls.
    Callables (e.g. a ``BestN.get_metric_fn`` lambda rebuilt per call) map to
    their qualname PLUS their captured closure values (two lambdas from the
    same source line closing over different metric names must not compare
    equal) and dataclass policies to their field structure, so re-specifying
    an identical configuration is idempotent instead of tripping the
    changed-options guard on lambda identity. The result contains only
    plain comparable values — arbitrary objects (arrays!) reduce to
    ``(type, repr)`` so ``==`` never goes ambiguous — and self-referential
    closures terminate via the ``_seen`` id-set."""
    import dataclasses

    if id(v) in _seen:
        return "<recursive>"
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        sub = _seen | {id(v)}
        return (
            type(v).__name__,
            tuple((f.name, _normalize_opt(getattr(v, f.name), sub)) for f in dataclasses.fields(v)),
        )
    if callable(v):
        key: Any = getattr(v, "__qualname__", repr(type(v)))
        cells = getattr(v, "__closure__", None)
        if cells:
            sub = _seen | {id(v)}
            try:
                key = (key, tuple(_normalize_opt(c.cell_contents, sub) for c in cells))
            except ValueError:  # an empty (yet-unassigned) cell
                pass
        return key
    if isinstance(v, (list, tuple)):
        sub = _seen | {id(v)}
        return tuple(_normalize_opt(x, sub) for x in v)
    if isinstance(v, (str, int, float, bool, bytes, type(None))):
        return v
    return (type(v).__name__, repr(v))


def atomic_write_text(target: epath.Path, text: str) -> None:
    """Crash-safe small-file write. Local filesystems get tmp-file +
    ``os.replace``; object stores commit whole objects atomically already,
    so a direct write is equivalent there (and rename is not atomic on GCS)."""
    if is_remote_path(target):
        target.write_text(text)
        return
    tmp = target.parent / f".{target.name}.tmp"
    tmp.write_text(text)
    os.replace(os.fspath(tmp), os.fspath(target))

#: Indicator file marking a valid run directory (reference: ``.dmlcloud``,
#: checkpoint.py:58-60).
INDICATOR_FILE = ".dmlcloud_tpu"

#: The requeue-verdict file a run leaves behind (doc/elasticity.md): one JSON
#: object answering the only question the requeue wrapper asks — should this
#: job be resubmitted, and why.
REQUEUE_FILE = "requeue.json"


def write_requeue_verdict(
    run_dir: Any, requeue: bool, reason: str, kind: str, **extra
) -> None:
    """Atomically write the requeue verdict for ``run_dir`` (schema v1)::

        {"v": 1, "requeue": true|false, "kind": "preemption"|"hang"|
         "exception"|"user-interrupt"|"completed", "reason": "...",
         "written_at": iso8601, ...extra}

    Call from ONE process (the root). ``extra`` carries kind-specific fields
    (epoch/global_step/save latency for preemptions, stragglers for hangs).
    A requeue wrapper (Slurm epilog, k8s controller) reads this instead of
    guessing from exit codes; see doc/elasticity.md for the contract."""
    import json

    record = {
        "v": 1,
        "requeue": bool(requeue),
        "kind": kind,
        "reason": reason,
        "written_at": datetime.now().isoformat(timespec="seconds"),
    }
    record.update(extra)
    target = as_run_path(run_dir) / REQUEUE_FILE
    atomic_write_text(target, json.dumps(record, indent=1))


def read_requeue_verdict(run_dir: Any) -> dict | None:
    """The run's requeue verdict, or None when absent/corrupt."""
    import json

    try:
        raw = json.loads((as_run_path(run_dir) / REQUEUE_FILE).read_text())
        if raw.get("v") == 1 and isinstance(raw.get("requeue"), bool):
            return raw
    except Exception:
        pass
    return None


def sanitize_filename(filename: str) -> str:
    return filename.replace("/", "_")


def generate_id(length: int = 8) -> str:
    """URL-safe random id (reference checkpoint.py:16-18)."""
    alphabet = string.ascii_lowercase + string.digits
    return "".join(random.choices(alphabet, k=length))


def generate_checkpoint_path(
    root: str | Path | epath.Path, name: str | None = None, dt: datetime | None = None
) -> epath.Path:
    """``{root}/{name}-{YYYY.MM.DD-HH.MM}-{id}`` — collision-free, sortable
    (reference checkpoint.py:21-34). ``root`` may be a ``gs://`` URI."""
    root = as_run_path(root)
    if name is None:
        name = "run"
    if dt is None:
        dt = datetime.now()
    stamp = dt.strftime("%Y.%m.%d-%H.%M")
    return root / sanitize_filename(f"{name}-{stamp}-{generate_id()}")


def find_slurm_checkpoint(root: str | Path | epath.Path) -> epath.Path | None:
    """Scan ``root`` for a run dir whose recorded Slurm job id matches the
    current job — how a requeued job finds its own previous checkpoint
    (reference checkpoint.py:37-48)."""
    job_id = slurm.slurm_job_id()
    if job_id is None:
        return None
    root = as_run_path(root)
    if not root.exists():
        return None
    for child in root.iterdir():
        ckpt = CheckpointDir(child)
        if ckpt.is_valid and ckpt.slurm_job_id == job_id:
            return child
    return None


class CheckpointDir:
    """A single run directory and its contract files.

    Layout (parity with reference checkpoint.py:56-70, plus ``state/``)::

        <path>/
          .dmlcloud_tpu     # indicator
          config.yaml       # experiment config snapshot
          log.txt           # stdout/stderr tee (utils/logging.py)
          .slurm-jobid      # written iff launched under Slurm
          state/            # Orbax CheckpointManager root (sharded tensors)
    """

    def __init__(self, path: str | Path | epath.Path):
        self.path = as_run_path(path)
        self._state_managers: dict[str | None, Any] = {}
        self._manager_opts: dict[str | None, tuple] = {}
        #: transient-filesystem-error policy for Orbax save dispatch: total
        #: attempts and the first backoff (doubles per retry, capped at 8s).
        #: Instance attributes so tests (and callers on flaky object stores)
        #: can tune them without process-global state.
        self.save_retries = 3
        self.save_backoff_s = 0.5

    # -- contract files -----------------------------------------------------
    @property
    def config_file(self) -> epath.Path:
        return self.path / "config.yaml"

    @property
    def indicator_file(self) -> epath.Path:
        return self.path / INDICATOR_FILE

    @property
    def log_file(self) -> epath.Path:
        return self.path / "log.txt"

    @property
    def slurm_file(self) -> epath.Path:
        return self.path / ".slurm-jobid"

    @property
    def requeue_file(self) -> epath.Path:
        return self.path / REQUEUE_FILE

    @property
    def state_dir(self) -> epath.Path:
        return self.path / "state"

    # -- validity (reference checkpoint.py:76-92) ---------------------------
    @property
    def exists(self) -> bool:
        return self.path.exists()

    @property
    def is_valid(self) -> bool:
        return self.path.is_dir() and self.indicator_file.exists()

    @property
    def slurm_job_id(self) -> str | None:
        if not self.slurm_file.exists():
            return None
        return self.slurm_file.read_text().strip()

    # -- creation (reference checkpoint.py:94-103; root-only by convention) --
    def create(self) -> None:
        if self.exists:
            raise RuntimeError(f"checkpoint dir already exists: {self.path}")
        self.path.mkdir(parents=True)
        self.indicator_file.touch()
        self.log_file.touch()
        if slurm.slurm_job_id() is not None:
            self.slurm_file.write_text(slurm.slurm_job_id())

    # -- config round-trip (reference checkpoint.py:105-117) ----------------
    def save_config(self, config: Any) -> None:
        as_config(config).save(self.config_file)

    def load_config(self) -> Config:
        return Config.load(self.config_file)

    # -- tensor state via Orbax (new capability vs reference) ---------------
    def has_state_manager(self, scope: str | None = None) -> bool:
        """Whether an Orbax manager for ``scope`` was already created (and
        its options therefore already bound)."""
        return scope in self._state_managers

    def state_manager(
        self, scope: str | None = None, max_to_keep: int | None = None, async_save: bool | None = None, **options
    ):
        """An Orbax CheckpointManager rooted at ``state/`` (or
        ``state/<scope>`` — stages checkpoint under their own scope so step
        ids never collide across stages). Collective: every process must
        participate in save/restore calls. Async saves copy device→host
        synchronously, so donated step buffers are safe.

        Defaults: ``max_to_keep=3``, ``async_save=True``. Options bind at
        FIRST creation per scope (e.g. in ``pre_stage``); explicitly passing
        different options for an existing scope raises."""
        explicit = max_to_keep is not None or async_save is not None or bool(options)
        # a preservation_policy owns retention outright — orbax rejects it
        # combined with max_to_keep, so the default only applies without one
        default_keep = None if "preservation_policy" in options else 3
        requested = (
            default_keep if max_to_keep is None else max_to_keep,
            True if async_save is None else async_save,
            tuple(sorted((k, _normalize_opt(v)) for k, v in options.items())),
        )
        if scope in self._state_managers:
            cached = self._manager_opts[scope]
            if explicit and requested != cached:
                raise RuntimeError(
                    f"Orbax manager for scope {scope!r} already exists with options "
                    f"{cached}; configure it via state_manager(...) BEFORE the first "
                    "save/restore for that scope (e.g. in pre_stage)"
                )
            return self._state_managers[scope]
        import orbax.checkpoint as ocp

        opts = ocp.CheckpointManagerOptions(
            max_to_keep=requested[0],
            enable_async_checkpointing=requested[1],
            **options,
        )
        root = self.state_dir / scope if scope else self.state_dir
        self._state_managers[scope] = ocp.CheckpointManager(root, options=opts)
        self._manager_opts[scope] = requested
        return self._state_managers[scope]

    def save_state(self, step: int, state: Any, scope: str | None = None, **kwargs) -> None:
        """Save a pytree of (possibly sharded) arrays under ``state/<step>``.

        Two durability features ride every save:

        - **bounded retry**: a transient filesystem error (``OSError``) at
          save dispatch is retried ``save_retries`` times with exponential
          backoff before the ORIGINAL error surfaces — an NFS hiccup or GCS
          503 at minute 590 of a 600-minute job must not cost the job.
        - **sharding sidecar**: the root records each leaf's PartitionSpec
          and the mesh shape (``meta/_sharding/<scope>/<step>.json``) so a
          later :meth:`restore_state` can rebuild shardings for a DIFFERENT
          mesh — the elastic-resume contract (doc/elasticity.md)."""
        import orbax.checkpoint as ocp

        from .telemetry import journal as _journal

        with _journal.span("checkpoint", label=scope, op="save", step=int(step)):
            self._retry_transient(
                lambda: self.state_manager(scope).save(
                    step, args=ocp.args.StandardSave(state), **kwargs
                ),
                what=f"save of step {step} (scope {scope!r})",
            )
        self._write_sharding_sidecar(scope, int(step), state)

    def _retry_transient(self, fn, what: str):
        """Run ``fn``, retrying transient filesystem errors (``OSError``)
        with bounded exponential backoff; the original error re-raises after
        the last attempt."""
        attempts = max(int(self.save_retries), 1)
        delay = float(self.save_backoff_s)
        first: OSError | None = None
        for attempt in range(1, attempts + 1):
            try:
                return fn()
            except OSError as e:
                first = first or e
                if attempt == attempts:
                    break
                _logger.warning(
                    "checkpoint %s hit a transient filesystem error (%s: %s); "
                    "retry %d/%d in %.1fs",
                    what, type(e).__name__, e, attempt, attempts - 1, delay,
                )
                time.sleep(delay)
                delay = min(delay * 2, 8.0)
        raise first

    # -- sharding sidecar (elastic resharded restore; doc/elasticity.md) -----
    def _sharding_sidecar_file(self, scope: str | None, step: int) -> epath.Path:
        # a dedicated subtree: ``meta/<scope>/`` belongs to the stage's
        # resume sidecars (stage.py _write_resume_sidecar enumerates it)
        return self.path / "meta" / "_sharding" / (scope or "_root") / f"{int(step)}.json"

    def _write_sharding_sidecar(self, scope: str | None, step: int, state: Any) -> None:
        """Root-only: record the mesh shape and every leaf's PartitionSpec at
        save time, then prune sidecars whose step Orbax no longer keeps.
        Best-effort — a failed sidecar write degrades restore to
        template/policy mode, never fails the save."""
        import json

        import jax
        from jax.sharding import NamedSharding

        if jax.process_index() != 0:
            return
        from .parallel import mesh as mesh_lib

        try:
            specs: dict[str, list] = {}
            mesh_shape: dict[str, int] = {}
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
                sharding = getattr(leaf, "sharding", None)
                if not isinstance(sharding, NamedSharding):
                    continue
                specs[mesh_lib.path_str(path)] = mesh_lib.spec_to_jsonable(sharding.spec)
                if not mesh_shape:
                    mesh_shape = {str(k): int(v) for k, v in sharding.mesh.shape.items()}
            record = {"v": 1, "mesh": mesh_shape, "specs": specs}
            target = self._sharding_sidecar_file(scope, step)
            target.parent.mkdir(parents=True, exist_ok=True)
            atomic_write_text(target, json.dumps(record))
            kept = set(int(s) for s in self.state_manager(scope).all_steps()) | {int(step)}
            for f in target.parent.glob("*.json"):
                if f.stem.isdigit() and int(f.stem) not in kept:
                    f.unlink(missing_ok=True)
        except Exception:
            _logger.warning(
                "could not write sharding sidecar for scope %r step %d "
                "(resharded restore will need an explicit template/policy)",
                scope, step, exc_info=True,
            )

    def read_sharding_sidecar(self, scope: str | None, step: int) -> dict | None:
        """The save-time sharding record for ``step`` (``{"mesh": {axis:
        size}, "specs": {leaf-path: spec}}``), or None when absent/corrupt."""
        import json

        try:
            raw = json.loads(self._sharding_sidecar_file(scope, step).read_text())
            if raw.get("v") == 1 and isinstance(raw.get("specs"), dict):
                return raw
        except Exception:
            pass
        return None

    def restore_template(
        self, step: int, scope: str | None = None, mesh: Any = None, policy: Any = None
    ) -> Any:
        """Build the abstract restore template for ``step`` targeted at
        ``mesh`` — WITHOUT the caller hand-building the state pytree. Tree
        structure, shapes, and dtypes come from Orbax's own checkpoint
        metadata; each leaf's sharding is the save-time PartitionSpec
        (sharding sidecar) re-targeted onto ``mesh`` via
        :func:`parallel.mesh.respec_for_mesh` — axes the new mesh lacks
        restore replicated, axes that stopped dividing relocate or drop.
        Without a sidecar (pre-elastic checkpoints), ``policy`` (a
        ``make_param_policy`` accepted value; default ``'replicate'``)
        decides the layout instead."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from .parallel import mesh as mesh_lib

        if mesh is None:
            raise ValueError("restore_template needs the target mesh")
        meta = self.state_manager(scope).item_metadata(step)
        if meta is None:
            raise ValueError(f"no checkpoint metadata for step {step} (scope {scope!r})")
        meta = meta.tree  # the saved pytree's own structure, out of orbax's TreeMetadata wrapper
        sidecar = self.read_sharding_sidecar(scope, step)
        specs = (sidecar or {}).get("specs", {})
        if sidecar is None:
            _logger.warning(
                "no sharding sidecar for scope %r step %d (checkpoint predates "
                "elastic resume?); restoring with policy %r",
                scope, step, policy or "replicate",
            )
        policy_fn = mesh_lib.make_param_policy(policy or "replicate")

        def leaf(path, m):
            p = mesh_lib.path_str(path)
            shape = tuple(m.shape)
            if p in specs:
                spec = mesh_lib.respec_for_mesh(
                    mesh_lib.spec_from_jsonable(specs[p]), shape, mesh
                )
            elif sidecar is not None:
                spec = PartitionSpec()  # saved unsharded (or spec unrecorded)
            else:
                spec = policy_fn(p, m, mesh)
            return jax.ShapeDtypeStruct(shape, m.dtype, sharding=NamedSharding(mesh, spec))

        return jax.tree_util.tree_map_with_path(leaf, meta)

    def restore_state(
        self,
        step: int | None = None,
        template: Any = None,
        scope: str | None = None,
        *,
        mesh: Any = None,
        policy: Any = None,
    ) -> Any:
        """Restore the latest (or a given) step.

        Three modes, most- to least-specified:

        - ``template=``: arrays restore with the template's exact
          shardings/dtypes (a template on a DIFFERENT mesh than the save is
          fine — Orbax reshards on read; this is how stages resume).
        - ``mesh=`` (no template): **elastic resharded restore** — the
          template is rebuilt from the checkpoint's own metadata plus the
          save-time sharding sidecar, re-targeted at ``mesh``
          (:meth:`restore_template`), so a save taken on N devices restores
          onto M devices without the caller knowing the state's structure.
          ``policy`` covers sidecar-less checkpoints.
        - neither: host numpy arrays with the SAVED shardings' layout —
          wrong on any other mesh (lint rule DML207 flags this pattern in
          mesh-building code)."""
        import orbax.checkpoint as ocp

        mgr = self.state_manager(scope)
        if step is None:
            step = mgr.latest_step()
        if step is None:
            return None
        if template is None and mesh is not None:
            import jax

            template = self.restore_template(step, scope=scope, mesh=mesh, policy=policy)
            restored = mgr.restore(step, args=ocp.args.StandardRestore(template))
            # Orbax may hand abstract-template restores back in host memory
            # (memory_kind=unpinned_host); re-place on the mesh's default
            # memory so the arrays are ready for the next compiled step.
            shardings = jax.tree_util.tree_map(lambda t: t.sharding, template)
            return jax.device_put(restored, shardings)
        if template is not None:
            return mgr.restore(step, args=ocp.args.StandardRestore(template))
        return mgr.restore(step)

    def latest_step(self, scope: str | None = None) -> int | None:
        return self.state_manager(scope).latest_step()

    _ALL_SCOPES = object()  # sentinel: scope=None names a real scope

    def wait_until_finished(self, scope: Any = _ALL_SCOPES) -> None:
        """Block until pending async saves commit — for one ``scope``, or for
        every manager (the default). The overlap engine's sync points
        (pre-save single-flight wait, stage end, run end, preemption exit)
        all land here; a scope with no manager yet is a no-op."""
        from .telemetry import journal as _journal

        if scope is not CheckpointDir._ALL_SCOPES:
            mgr = self._state_managers.get(scope)
            if mgr is not None:
                with _journal.span("checkpoint", label=scope, op="wait"):
                    mgr.wait_until_finished()
            return
        with _journal.span("checkpoint", op="wait_all"):
            for mgr in self._state_managers.values():
                mgr.wait_until_finished()

    def close(self) -> None:
        for mgr in self._state_managers.values():
            mgr.close()
        self._state_managers = {}
        self._manager_opts = {}

    def __str__(self) -> str:
        return str(self.path)

    def __repr__(self) -> str:
        return f"CheckpointDir({self.path!r})"
