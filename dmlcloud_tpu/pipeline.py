"""TrainingPipeline: the experiment orchestrator.

Capability parity with /root/reference/dmlcloud/pipeline.py:20-331 — config
container, registries for models/optimizers/schedulers/datasets/stages,
checkpoint + wandb enablement, run lifecycle with cleanup guard, barriers with
timeout, diagnostics — re-based on the TPU runtime:

- device selection (pipeline.py:231-242) becomes mesh construction: the
  pipeline owns a ``jax.sharding.Mesh`` (default: one ``data`` axis over all
  devices — DDP semantics) that every stage's compiled step is sharded over.
- ``register_model``'s DDP wrap (pipeline.py:72-74) becomes laying params out
  on the mesh under a sharding policy ('replicate' == DDP, 'fsdp' == ZeRO-3,
  rule list == tensor parallel).
- the gloo side-group for timeout barriers (pipeline.py:226-229) becomes the
  coordination-service monitored barrier (parallel/runtime.py).
- optimizers are optax transformations; schedulers are optax schedules.
- checkpointing keeps the directory contract and adds Orbax tensor state
  (checkpoint.py).
"""

from __future__ import annotations

import logging
import re
from contextlib import contextmanager
from dataclasses import dataclass, field
from datetime import datetime
from typing import Any, Callable, Optional

import jax

from .checkpoint import CheckpointDir, find_slurm_checkpoint, generate_checkpoint_path
from .metrics import MetricTracker, Reduction
from .parallel import mesh as mesh_lib
from .parallel import runtime
from .stage import Stage
from .utils.config import Config, as_config
from .utils.logging import IORedirector, add_log_handlers, experiment_header, general_diagnostics
from .utils.wandb import wandb, wandb_is_initialized, wandb_set_startup_timeout


@dataclass
class ModelEntry:
    name: str
    module: Any  # flax module or None
    apply_fn: Callable
    params: Any
    policy: Any = "replicate"
    extras: Any = None  # non-trained collections (batch_stats, ...)
    ema: Any = None  # EMA shadow published by a stage with ema_decay() > 0


class TrainingPipeline:
    def __init__(
        self,
        config: Any = None,
        name: Optional[str] = None,
        lint: Optional[str] = None,
        verify: Optional[str] = None,
        hbm_budget: Optional[int] = None,
        sanitize: Optional[str] = None,
        compile_cache: Any = True,
        precompile: bool = False,
        buckets: Any = None,
        telemetry: Any = None,
    ):
        """``lint`` arms the TPU-hazard linter (dmlcloud_tpu.lint) over every
        registered Stage subclass's source at run start: ``"warn"`` logs the
        findings, ``"error"`` raises ``lint.LintError`` before any device
        work happens. None (default) skips linting — the CLI
        (``python -m dmlcloud_tpu lint``) and the self-lint test remain the
        review-time nets.

        ``verify`` arms the IR-level verifier (dmlcloud_tpu.lint.ir; doc/
        lint.md DML6xx) over the precompiled step executables at stage
        start: each AOT-compiled train/val signature is re-audited as the
        program XLA will actually run — donation that jit silently
        dropped (DML601), collective/sharding axes that don't resolve
        against the mesh (DML602), host callbacks baked into the step
        (DML603), and — when ``hbm_budget`` (bytes) is declared —
        estimated peak memory over budget (DML604). The arm re-uses the
        executables ``precompile=True`` already built, so it adds zero
        compiles; it therefore only runs where precompilation runs.
        ``"warn"`` logs findings, ``"error"`` raises ``lint.LintError``
        before the data loop. None (default) skips it — the CLI
        (``python -m dmlcloud_tpu verify``) remains the review-time net.

        ``sanitize`` arms the RUNTIME sanitizer (dmlcloud_tpu.lint.sanitize)
        — the dynamic companion of the static pass: each stage's epoch runs
        under a device-to-host conversion probe (implicit ``np.asarray`` of
        a device value outside a StallTimer-accounted block), step dispatch
        is checked for host numpy leaves (an implicit host-to-device
        transfer), and ``"error"`` additionally arms jax's
        ``transfer_guard`` + ``jax_debug_nans`` for the window. ``"warn"``
        reports each violation site once (log + ``sanitizer`` telemetry
        span + ``pipeline.sanitizer_findings``) and continues; ``"error"``
        raises ``lint.SanitizerError`` at the violation. None/``"off"``
        (default) changes nothing — not even a context manager enters.

        The cold-start killers (dmlcloud_tpu.compile; doc/performance.md §4):

        - ``compile_cache``: persistent XLA compilation cache, on by
          default. The directory is ``$JAX_COMPILATION_CACHE_DIR`` when
          that is set — point every host of a pod at the same shared-FS
          dir (entries are content-addressed; concurrent writers are safe;
          only process 0 logs stats) — else a path passed here, else
          ``<checkout>/.jax_cache`` (compile/cache.py). None/False leaves
          jax's config untouched.
        - ``precompile``: default for ``Stage.precompile()`` — AOT-compile
          the train/val steps at stage start against the first batch's
          abstract spec, before the data loop.
        - ``buckets``: default for ``Stage.buckets()`` — pad ragged batch
          dims to this ascending size set (with a zero-weight sample mask)
          so the compiled-signature count stays bounded.

        ``telemetry`` arms the flight recorder (dmlcloud_tpu.telemetry;
        doc/observability.md): a per-host span journal (JSONL, merged by
        ``python -m dmlcloud_tpu timeline <run_dir>``), the goodput/MFU
        ledger (``misc/goodput``/``misc/mfu`` + a root-only end-of-run
        table), and the hang watchdog (forensics dump when step/span
        progress stops). ``True`` journals into ``<checkpoint_dir>/
        telemetry`` (or ``./telemetry`` without checkpointing / on remote
        checkpoint paths); a path selects the directory; a dict configures
        ``{"dir", "hang_threshold_s" (default 600), "watchdog_interval_s"
        (10), "ring_size" (1024)}``. None/False (default): fully off — the
        instrumentation points reduce to one attribute read."""
        if lint not in (None, "warn", "error"):
            raise ValueError(f'lint must be None, "warn" or "error", got {lint!r}')
        if verify not in (None, "warn", "error"):
            raise ValueError(f'verify must be None, "warn" or "error", got {verify!r}')
        if sanitize not in (None, "off", "warn", "error"):
            raise ValueError(f'sanitize must be None, "off", "warn" or "error", got {sanitize!r}')
        self.config: Config = as_config(config)
        self.name = name
        self._lint_mode = lint
        self._verify_mode = verify
        self._hbm_budget = None if hbm_budget is None else int(hbm_budget)
        #: findings of the last verify preflight (stage.py fills this)
        self.verify_findings: list = []
        from .lint.sanitize import Sanitizer

        self._sanitizer = Sanitizer(sanitize or "off", logger=logging.getLogger("dmlcloud_tpu"))
        self._compile_cache = compile_cache
        self._compile_cache_dir: str | None = None
        self._precompile = bool(precompile)
        self._buckets = tuple(buckets) if buckets else None
        if telemetry is not None and not isinstance(telemetry, (bool, str, dict)) and not hasattr(telemetry, "__fspath__"):
            raise ValueError(
                f"telemetry must be None/bool, a directory path, or a config dict, got {telemetry!r}"
            )
        self._telemetry_cfg = telemetry
        self.telemetry_dir: str | None = None
        self._journal = None
        self._watchdog = None
        self._run_span_t0: float | None = None

        self.logger = logging.getLogger("dmlcloud_tpu")
        self.checkpoint_dir: CheckpointDir | None = None
        self.io_redirector = None
        self.resumed: bool | None = None
        self.tracker = MetricTracker()
        self.mesh = None
        self.root_key = None
        self.start_time = None
        self.stop_time = None
        self.current_stage = None

        self.wandb = False
        self._wandb_opts: dict | None = None
        self._wandb_timeout = 360
        self._tensorboard_dir: str | None = None
        self._tb_writer = None

        self._preemption = runtime.PreemptionGuard(signals=())
        self._verdict_written = False
        self._verdict_kind: Optional[str] = None

        self.stages: list[Stage] = []
        self.datasets: dict[str, Any] = {}
        self.models: dict[str, ModelEntry] = {}
        self.optimizers: dict[str, Any] = {}
        self.schedulers: dict[str, Any] = {}
        self._optimizer_model: dict[str, str | None] = {}

    # ------------------------------------------------------------------ mesh
    @property
    def checkpointing_enabled(self) -> bool:
        return self.checkpoint_dir is not None

    @property
    def telemetry_armed(self) -> bool:
        """True between telemetry arming at run start and teardown."""
        return self._journal is not None

    @property
    def sanitizer_findings(self):
        """Violations the runtime sanitizer recorded this run (Finding
        schema; empty when ``sanitize`` is off or nothing tripped)."""
        return list(self._sanitizer.findings)

    def set_mesh(self, mesh_or_axes) -> None:
        """Set the device mesh (a ``jax.sharding.Mesh`` or an axes dict like
        ``{'data': -1}`` / ``{'data': 2, 'model': 4}``). Default if never
        called: a single ``data`` axis over all devices."""
        if isinstance(mesh_or_axes, dict):
            self.mesh = mesh_lib.create_mesh(mesh_or_axes)
        else:
            self.mesh = mesh_or_axes

    # ----------------------------------------------------------- registries
    def register_model(
        self,
        name: str,
        model: Any = None,
        params: Any = None,
        apply_fn: Callable | None = None,
        sharding: Any = "replicate",
        init_args: tuple | None = None,
        init_rng: int | jax.Array = 0,
        verbose: bool = True,
    ):
        """Register a model and lay its params out on the mesh.

        Accepts a flax module (``apply_fn = model.apply``; if ``params`` is
        None they are initialised from ``init_args`` example inputs), or an
        explicit ``(apply_fn, params)`` pair. ``sharding`` is the param
        policy: 'replicate' (DDP semantics, reference pipeline.py:72-74),
        'fsdp', a T5X-style rule list, or a callable.
        """
        if name in self.models:
            raise ValueError(f"Model with name {name} already exists")
        if self.mesh is None:
            self._init_mesh()

        extras = None
        if model is not None and hasattr(model, "apply") and hasattr(model, "init"):
            apply_fn = model.apply
            if params is None:
                if init_args is None:
                    raise ValueError("params=None requires init_args example inputs for module.init")
                rng = jax.random.PRNGKey(init_rng) if isinstance(init_rng, int) else init_rng
                params = model.init(rng, *init_args)
        elif apply_fn is None:
            if not callable(model):
                raise ValueError("register_model needs a flax module, or apply_fn + params")
            apply_fn = model

        # flax variables: split trained params from mutable collections
        if isinstance(params, dict) and "params" in params:
            variables = dict(params)
            params = variables.pop("params")
            extras = variables or None

        params = mesh_lib.shard_pytree(params, self.mesh, sharding)
        if extras is not None:
            extras = mesh_lib.shard_pytree(extras, self.mesh, sharding)
        self.models[name] = ModelEntry(
            name=name, module=model, apply_fn=apply_fn, params=params, policy=sharding, extras=extras
        )

        if verbose:
            n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params) if hasattr(x, "size"))
            msg = f'Model "{name}":\n'
            msg += f"    - Parameters: {n_params / 1e6:.1f} M\n"
            msg += f"    - Sharding policy: {sharding if isinstance(sharding, str) else 'custom rules'}\n"
            msg += f"    - Mesh: {dict(self.mesh.shape) if self.mesh is not None else None}"
            self.logger.info(msg)

    def register_optimizer(self, name: str, optimizer, scheduler=None, model: str | None = None):
        """Register an optax transformation (and optionally its schedule, for
        LR tracking parity with reference stage.py:316-318)."""
        if name in self.optimizers:
            raise ValueError(f"Optimizer with name {name} already exists")
        self.optimizers[name] = optimizer
        self._optimizer_model[name] = model
        if scheduler is not None:
            self.schedulers[name] = scheduler

    def register_dataset(self, name: str, dataset: Any, verbose: bool = True):
        """Register a per-process dataset shard under ``name`` ('train'/'val'
        are the names TrainValStage looks up). Any iterable of batches works:
        a DataPipeline, a DataLoader shim, or a plain list."""
        if name in self.datasets:
            raise ValueError(f"Dataset with name {name} already exists")
        self.datasets[name] = dataset
        if verbose:
            try:
                per_worker: Any = len(dataset)
                total: Any = f"~{per_worker * runtime.world_size()}"
            except TypeError:  # iterable-only pipelines carry no length
                per_worker = total = "unknown"
            self.logger.info(
                'Dataset "%s": %s batches/worker, %s total across %d processes',
                name, per_worker, total, runtime.world_size(),
            )

    def append_stage(self, stage: Stage, max_epochs: Optional[int] = None, name: Optional[str] = None):
        if not isinstance(stage, Stage):
            raise ValueError("stage must be a Stage object")
        stage.pipeline = self
        stage.max_epochs = max_epochs
        # unique name: it keys the stage's checkpoint scope (state/<name>).
        # Explicit duplicates are an error (like register_model); anonymous
        # same-class stages get a numeric suffix.
        existing = {s.name for s in self.stages}
        if name is not None:
            # the name keys filesystem paths (state/<name>, meta/<name>); an
            # unconstrained string like "../other" would escape the checkpoint dir
            if not re.fullmatch(r"[A-Za-z0-9._-]+", name) or name in (".", ".."):
                raise ValueError(
                    f"Stage name {name!r} is invalid: must match [A-Za-z0-9._-]+ "
                    "(it names checkpoint subdirectories)"
                )
            if name in existing:
                raise ValueError(f"Stage with name {name!r} already exists")
            stage.name = name
        else:
            base = type(stage).__name__
            unique, i = base, 2
            while unique in existing:
                unique, i = f"{base}_{i}", i + 1
            stage.name = unique
        self.stages.append(stage)

    # -- registry lookups used by TrainValStage -----------------------------
    def _model_entry(self, name: str | None = None) -> ModelEntry:
        if name is not None:
            if name not in self.models:
                raise ValueError(f"No model named {name!r} registered")
            return self.models[name]
        if len(self.models) == 1:
            return next(iter(self.models.values()))
        if not self.models:
            raise ValueError("No model registered. Call register_model() (e.g. in pre_stage).")
        raise ValueError("Multiple models registered; override Stage.model_name() to pick one.")

    def _optimizer_for(self, model_name: str):
        if not self.optimizers:
            raise ValueError("No optimizer registered. Call register_optimizer() (e.g. in pre_stage).")
        explicit = [n for n, m in self._optimizer_model.items() if m == model_name]
        if len(explicit) > 1:
            raise ValueError(
                f"Multiple optimizers ({explicit}) registered for model {model_name!r}; "
                "a model can only be trained by one optimizer per stage."
            )
        if explicit:
            return self.optimizers[explicit[0]]
        unbound = [n for n, m in self._optimizer_model.items() if m is None]
        # mirror _model_entry's ambiguity error: with several models AND
        # several unbound optimizers there is no defensible pairing — the old
        # behavior silently trained every model with the first optimizer
        if len(unbound) > 1 and len(self.models) > 1:
            raise ValueError(
                f"Multiple unbound optimizers ({unbound}) and multiple models registered; "
                "pass model=... to register_optimizer() to bind each optimizer to its model."
            )
        if unbound:
            return self.optimizers[unbound[0]]
        raise ValueError(
            f"No optimizer registered for model {model_name!r} and no unbound optimizer "
            "to fall back on. Call register_optimizer(model=...)."
        )

    # -------------------------------------------------------- checkpointing
    def enable_checkpointing(self, root: str, resume: bool = False):
        """Reference pipeline.py:116-137: reuse a valid dir when resuming,
        rediscover by Slurm job id on requeue, else generate a fresh path
        agreed across processes via broadcast."""
        if self.checkpointing_enabled:
            raise ValueError("Checkpointing already enabled")

        path = None
        if resume and CheckpointDir(root).is_valid:
            path = root
            self.resumed = True
        elif resume and (slurm_path := find_slurm_checkpoint(root)):
            path = slurm_path
            self.resumed = True

        if path is None:
            path = generate_checkpoint_path(root=root, name=self.name)
            path = runtime.broadcast_object(path)
            self.resumed = False

        self.checkpoint_dir = CheckpointDir(path)

    def enable_wandb(
        self,
        project: str | None = None,
        entity: str | None = None,
        group: str | None = None,
        tags: list[str] | None = None,
        startup_timeout: int = 360,
        **kwargs,
    ):
        """Send the tracker's per-epoch metrics to Weights & Biases.

        Only stores the run options here; the root process opens the actual
        wandb run during ``_pre_run`` (after the runtime and config are
        final). Extra ``kwargs`` pass straight through to ``wandb.init``."""
        import wandb as _wandb  # noqa: F401 — surface a missing install at call time

        self._wandb_opts = dict(
            entity=entity,
            project=project or self.name,
            group=group,
            tags=tags,
            **kwargs,
        )
        self._wandb_timeout = startup_timeout
        self.wandb = True

    def enable_tensorboard(self, logdir: str | None = None):
        """Write per-epoch tracker scalars as TensorBoard event files (the
        writer itself is root-only; needs ``tensorboardX``). Default logdir:
        ``<checkpoint_dir>/tb`` resolved at run start — alongside any
        ``jax.profiler`` traces, so one ``tensorboard --logdir`` shows the
        curves and the device timeline of the same run. A third
        observability channel the reference lacks (console table + wandb
        are the other two)."""
        import tensorboardX  # noqa: F401 — surface a missing install at call time

        self._tensorboard_dir = logdir if logdir is not None else "__checkpoint__"
        return self

    @runtime.root_only
    def _start_wandb(self):
        import wandb as _wandb

        wandb_set_startup_timeout(self._wandb_timeout)
        _wandb.init(
            config=self.config.to_dict(resolve=True),
            name=self.name,
            **self._wandb_opts,
        )

    # -------------------------------------------------------------- metrics
    def track_reduce(
        self,
        name: str,
        value: Any,
        step: int | None = None,
        reduction: Reduction = Reduction.MEAN,
        dim: list[int] | None = None,
        reduce_globally: bool = True,
    ):
        """Buffer ``value`` under an epoch-end reduction. The metric is
        registered on first use; the reduction arguments only take effect
        then (subsequent calls just append)."""
        if name not in self.tracker:
            self.tracker.register_metric(name, reduction, dim, reduce_globally)
        self.tracker.track(name, value)

    def track(self, name: str, value: Any, step: int | None = None):
        """Record an already-final (unreduced, process-local) value for the
        current epoch."""
        if name not in self.tracker:
            self.tracker.register_metric(name)
        self.tracker.track(name, value)

    def barrier(self, timeout=None):
        """All-process barrier with timeout (reference pipeline.py:191-196)."""
        runtime.barrier("pipeline", timeout if timeout is not None else 600.0)

    # -------------------------------------------------------- preemption
    #: back-compat views over the PreemptionGuard (parallel/runtime.py),
    #: which owns the signal handlers and the cross-rank drain decision
    @property
    def _preempted(self) -> bool:
        return self._preemption.triggered

    @_preempted.setter
    def _preempted(self, v: bool) -> None:
        self._preemption.triggered = bool(v)

    @property
    def _preemption_enabled(self) -> bool:
        return self._preemption.armed

    @_preemption_enabled.setter
    def _preemption_enabled(self, v: bool) -> None:
        self._preemption.armed = bool(v)

    @property
    def _prev_signal_handlers(self) -> dict:
        return self._preemption._prev

    def enable_preemption_handling(self, signals: tuple[str, ...] | None = ("SIGTERM",)):
        """Exit cleanly at the next save boundary when any of ``signals``
        arrives on ANY rank (Cloud TPU preemption sends SIGTERM; Slurm jobs
        typically arrange ``--signal=USR1@60`` -> pass ``("SIGUSR1",)``, or
        pass ``signals=None`` for the guard's environment-aware default:
        SIGTERM + SIGINT, plus SIGUSR1 inside a Slurm step).

        With epoch checkpointing the drain lands at the epoch boundary
        (the finished epoch has already auto-saved); with
        ``checkpoint_every_steps()`` armed it lands at the next step-save
        boundary mid-epoch. Either way the stage is NOT marked stopped and
        the root writes a requeue verdict (``requeue.json``,
        doc/elasticity.md) so a requeued run resumes where this one drained
        — on whatever mesh the new allocation provides (resharded restore).
        This is TPU-side scope: the reference's fault model is Slurm
        requeue after the fact (reference checkpoint.py:37-48) with no
        in-flight signal handling."""
        # re-arming: restore the ORIGINAL dispositions first, so the new
        # guard's install records them (not our previous handler) as prev
        self._preemption.uninstall()
        self._preemption = runtime.PreemptionGuard(signals=signals).install()

    def _preemption_coordinated(self) -> bool:
        """Whether ANY rank caught a preemption signal (see
        ``PreemptionGuard.coordinated``)."""
        return self._preemption.coordinated()

    def _write_requeue_verdict(
        self, requeue: bool, kind: str, reason: str, force: bool = False, **extra
    ) -> None:
        """Root-only, first-writer-wins requeue verdict for this run (the
        preemption/hang verdict must not be stomped by the teardown's
        generic classification; ``force`` is for the one legitimate
        supersession — a run that RECOVERED from a watchdog-flagged stall
        and completed). No-op without a checkpoint dir — there is nowhere
        durable to resume from, so a verdict would be noise."""
        if (self._verdict_written and not force) or self.checkpoint_dir is None or not runtime.is_root():
            return
        from .checkpoint import is_remote_path, write_requeue_verdict

        try:
            if not is_remote_path(self.checkpoint_dir.path) and not self.checkpoint_dir.exists:
                return  # e.g. run failed before _init_checkpointing created it
            write_requeue_verdict(self.checkpoint_dir.path, requeue, reason, kind, **extra)
            self._verdict_written = True
            self._verdict_kind = kind
            self.logger.info(
                "requeue verdict: requeue=%s (%s) — %s", requeue, kind, reason
            )
        except Exception:
            self.logger.warning("could not write requeue verdict", exc_info=True)

    def _classify_failure(self, exc: BaseException) -> tuple[bool, str, str]:
        """(requeue, kind, reason) for an uncaught exception — the automated
        half of the flight recorder's post-mortem: deterministic failures
        (NaN loss, lint errors) must NOT be requeued (they recur), while
        transient infrastructure failures (stragglers/hangs, filesystem
        errors) should be."""
        if isinstance(exc, KeyboardInterrupt):
            return False, "user-interrupt", "run aborted by user (KeyboardInterrupt)"
        if isinstance(exc, runtime.BarrierTimeout):
            return True, "hang", (
                f"barrier '{exc.tag}' timed out; straggler ranks {exc.stragglers or 'unknown'}"
                " — transient by default, forensics dumped"
            )
        if isinstance(exc, FloatingPointError):
            return False, "exception", f"non-finite loss is deterministic: {exc}"
        if isinstance(exc, OSError):
            return True, "exception", (
                f"filesystem/IO error ({type(exc).__name__}: {exc}) — transient by default"
            )
        return False, "exception", f"{type(exc).__name__}: {exc}"

    # ------------------------------------------------------------ lifecycle
    def run(self):
        """Run all registered stages sequentially."""
        with _run_guard(self):
            self._pre_run()
            for stage in self.stages:
                self.current_stage = stage
                stage.run()
                # the stage's own coordinated decision — already in lockstep
                # across ranks, no extra collective needed here
                if getattr(stage, "_preempt_exit", False):
                    self.logger.info("preemption requested; skipping remaining stages")
                    extra = {
                        "stage": stage.name,
                        "epoch": stage.current_epoch,
                        "mid_epoch": bool(getattr(stage, "_mid_epoch_exit", False)),
                    }
                    lat = getattr(stage, "_last_save_latency_s", None)
                    if lat is not None:
                        extra["save_on_preempt_latency_s"] = round(float(lat), 4)
                    sig = self._preemption.signal_name or "coordinated-drain"
                    self._write_requeue_verdict(
                        True, "preemption",
                        f"drained cleanly on {sig}; state saved at the last boundary, resumable",
                        **extra,
                    )
                    break
            self._post_run()

    # user hooks (reference pipeline.py:208-215)
    def pre_run(self):
        pass

    def post_run(self):
        pass

    def resume_run(self):
        pass

    # internals
    def _init_mesh(self):
        if self.mesh is None:
            self.mesh = mesh_lib.create_mesh({mesh_lib.DATA: -1})
        runtime._cpu_safety_flags()

    def _lint_stages(self) -> None:
        """Lint every registered Stage subclass's source (the runtime arm of
        dmlcloud_tpu.lint — catches hazards in stages assembled dynamically,
        where no CLI run ever sees the file). Classes whose source is
        unavailable (REPL, exec) are skipped: the linter is a net, not a
        gate on how code gets defined."""
        if self._lint_mode is None:
            return
        import inspect
        import textwrap

        from .lint import LintError, lint_source

        findings = []
        seen: set[type] = set()
        for stage in self.stages:
            cls = type(stage)
            # framework-shipped stages are covered by the repo's own
            # self-lint gate; lint only user subclasses, each class once
            if cls in seen or cls.__module__.startswith("dmlcloud_tpu."):
                continue
            seen.add(cls)
            try:
                lines, start = inspect.getsourcelines(cls)
                path = inspect.getsourcefile(cls) or f"<{cls.__name__}>"
            except (OSError, TypeError):
                continue
            # re-anchor to the original line numbers so findings are clickable
            src = "\n" * (start - 1) + textwrap.dedent("".join(lines))
            findings.extend(lint_source(src, path=path))
        if not findings:
            return
        report = "\n".join(f.format() for f in findings)
        if self._lint_mode == "error":
            raise LintError(
                f"TPU-hazard linter found {len(findings)} problem(s) in registered "
                f"stages (doc/lint.md; suppress with '# dmllint: disable=ID'):\n{report}",
                findings,
            )
        self.logger.warning("TPU-hazard linter findings in registered stages:\n%s", report)

    def _pre_run(self):
        if len(self.stages) == 0:
            raise ValueError("No stages defined. Use append_stage() to add stages to the pipeline.")
        self._verdict_written = False
        self._verdict_kind = None
        self._lint_stages()
        if self._compile_cache not in (None, False):
            # before ANY compilation (incl. the collectives the runtime
            # bootstrap below may compile) so every program is cacheable
            from .compile.cache import configure_cache

            self._compile_cache_dir = configure_cache(self._compile_cache)
        if not runtime.is_initialized():
            runtime.init_auto()

        self._init_mesh()
        if self.root_key is None:
            self.root_key = jax.random.PRNGKey(int(self.config.get("seed", 0)))

        # prevent checkpoint-dir creation before every process searched for it
        # (reference pipeline.py:244-246)
        self.barrier(timeout=600)
        if self.checkpointing_enabled:
            self._init_checkpointing()
        self._arm_telemetry()

        if self.wandb:
            self._start_wandb()
        if self._tensorboard_dir is not None and runtime.is_root():
            from .utils.tensorboard import TensorBoardWriter

            tb_dir = self._tensorboard_dir
            if tb_dir == "__checkpoint__":
                if self.checkpoint_dir is None:
                    raise ValueError(
                        "enable_tensorboard() without a logdir needs checkpointing enabled "
                        "(the default logdir is <checkpoint_dir>/tb) — pass an explicit logdir"
                    )
                tb_dir = str(self.checkpoint_dir.path / "tb")
            self._tb_writer = TensorBoardWriter(tb_dir)

        self.barrier(timeout=600)
        self.start_time = datetime.now()

        add_log_handlers(self.logger)
        header = "\n" + experiment_header(self.name, str(self.checkpoint_dir) if self.checkpoint_dir else None, self.start_time)
        self.logger.info(header)

        if self.resumed:
            self._resume_run()

        diagnostics = general_diagnostics()
        diagnostics += "\n* MESH:\n"
        diagnostics += f"    - axes: {dict(self.mesh.shape)}\n"
        local_desc = f"{runtime.local_device_count()}x {jax.local_devices()[0].device_kind}"
        devices = runtime.all_gather_object(local_desc)
        diagnostics += "\n".join(f"    - [Process {i}] {d}" for i, d in enumerate(devices))
        diagnostics += "\n* CONFIG:\n"
        diagnostics += "\n".join(f"    {line}" for line in self.config.to_yaml(resolve=True).splitlines())
        self.logger.info(diagnostics)
        if self._compile_cache_dir is not None and runtime.is_root():
            self.logger.info("persistent compilation cache: %s", self._compile_cache_dir)

        self.pre_run()

    def _arm_telemetry(self):
        """Start the flight recorder: journal + goodput + hang watchdog
        (dmlcloud_tpu.telemetry). Per-host — every rank journals and
        watches; only the root prints the end-of-run ledger."""
        cfg = self._telemetry_cfg
        if cfg is None or cfg is False:
            return
        import os

        from .checkpoint import is_remote_path
        from .telemetry import journal as journal_mod
        from .telemetry.watchdog import HangWatchdog

        opts = dict(cfg) if isinstance(cfg, dict) else {}
        tdir = opts.get("dir")
        if tdir is None and not isinstance(cfg, (bool, dict)):
            tdir = os.fspath(cfg)
        if tdir is None:
            # journals are plain local appends; a gs://... checkpoint root
            # cannot take them, so fall back to the working directory
            if self.checkpoint_dir is not None and not is_remote_path(self.checkpoint_dir.path):
                tdir = str(self.checkpoint_dir.path / "telemetry")
            else:
                tdir = os.path.abspath("telemetry")
        self.telemetry_dir = str(tdir)
        self._journal = journal_mod.SpanJournal(
            self.telemetry_dir,
            rank=runtime.rank(),
            ring_size=int(opts.get("ring_size", 1024)),
        )
        journal_mod.activate(self._journal)
        self._journal.start()
        forensics_dir = os.path.join(self.telemetry_dir, os.pardir, "forensics")
        if self.checkpoint_dir is not None and not is_remote_path(self.checkpoint_dir.path):
            forensics_dir = str(self.checkpoint_dir.path / "forensics")
        self._watchdog = HangWatchdog(
            os.path.normpath(forensics_dir),
            rank=runtime.rank(),
            world_size=runtime.world_size(),
            threshold_s=float(opts.get("hang_threshold_s", 600.0)),
            interval_s=float(opts.get("watchdog_interval_s", 10.0)),
            journal=self._journal,
        )
        self._journal.on_emit = self._watchdog.notify

        def _hang_verdict(reason: str) -> None:
            # the forensics dump's requeue-wrapper counterpart: a hang is
            # transient by default (requeue and let the watchdog's evidence
            # drive a deeper look), and the verdict names the stragglers
            extra = {}
            stragglers = runtime.barrier_state().get("stragglers")
            if stragglers:
                extra["stragglers"] = stragglers
            self._write_requeue_verdict(True, "hang", reason, **extra)

        self._watchdog.on_dump = _hang_verdict
        self._watchdog.start()
        self._run_span_t0 = journal_mod.now()
        if runtime.is_root():
            self.logger.info(
                "telemetry armed: journal %s, forensics %s (hang threshold %.0fs)",
                self.telemetry_dir, self._watchdog.dump_dir, self._watchdog.threshold_s,
            )

    def _telemetry_ledger(self):
        """Root-only end-of-run goodput ledger: log the table and persist
        ``goodput.json`` next to the journals."""
        from .telemetry import journal as journal_mod
        from .telemetry.goodput import ledger_from_tracker

        if self._run_span_t0 is not None:
            journal_mod.emit("run", self._run_span_t0, label=self.name or "run")
        ledger = ledger_from_tracker(self.tracker)
        if not runtime.is_root():
            return
        if ledger.rows:
            self.logger.info("\n%s", ledger.format_table())
            # advisory-only knob suggestions (goodput advisor): printed,
            # never auto-applied — the same lines `diag --run` derives
            for line in ledger.advise():
                self.logger.warning("goodput advisor: %s", line)
        import json
        import os

        try:
            with open(os.path.join(self.telemetry_dir, "goodput.json"), "w", encoding="utf-8") as f:
                json.dump(ledger.to_dict(), f)
        except OSError:
            self.logger.warning("could not write %s/goodput.json", self.telemetry_dir, exc_info=True)

    def _disarm_telemetry(self, exc: BaseException | None = None):
        """Teardown half of ``_arm_telemetry`` — always runs (run guard).
        An uncaught exception triggers a forensics dump first: the flight
        recorder's whole point is that the crash leaves evidence behind."""
        from .telemetry import journal as journal_mod

        if self._watchdog is not None:
            if exc is not None and not isinstance(exc, KeyboardInterrupt):
                try:
                    path = self._watchdog.dump(f"uncaught exception: {type(exc).__name__}: {exc}")
                    self.logger.info("forensics dumped to %s", path)
                except Exception:
                    self.logger.warning("forensics dump failed", exc_info=True)
            self._watchdog.stop()
            self._watchdog = None
        if self._journal is not None:
            if journal_mod.active_journal() is self._journal:
                journal_mod.deactivate()
            self._journal.close()
            self._journal = None

    @runtime.root_only
    def _init_checkpointing(self):
        if not self.checkpoint_dir.is_valid:
            self.checkpoint_dir.create()
            self.checkpoint_dir.save_config(self.config)
        self.io_redirector = IORedirector(self.checkpoint_dir.log_file)
        self.io_redirector.install()

    def _resume_run(self):
        self.logger.info(f"Resuming training from checkpoint: {self.checkpoint_dir}")
        self.resume_run()

    def _post_run(self):
        self.stop_time = datetime.now()
        if self.checkpoint_dir is not None:
            self.checkpoint_dir.wait_until_finished()
        if self.telemetry_armed:
            self._telemetry_ledger()
        # shared-FS aware: every process shares the cache dir, process 0 logs
        if self._compile_cache_dir is not None and runtime.is_root():
            from .compile.cache import cache_stats

            s = cache_stats()
            self.logger.info(
                "compile cache: %d entries (%.1f MB) at %s — this process: "
                "%d AOT hit(s), %d miss(es), %.0f ms compiling",
                s["entries"], s["size_bytes"] / 1e6, s["dir"],
                s["aot_hits"], s["aot_misses"], s["aot_compile_ms"],
            )
        self.logger.info(f"Finished training in {self.stop_time - self.start_time} ({self.stop_time})")
        if self.checkpointing_enabled:
            self.logger.info(f"Outputs have been saved to {self.checkpoint_dir}")
        # a run that got here without a preemption verdict finished for real:
        # tell the requeue wrapper to stand down. A survived watchdog stall
        # is the one verdict completion supersedes (the run recovered).
        self._write_requeue_verdict(
            False, "completed", "run finished all stages",
            force=(self._verdict_kind == "hang"),
        )
        self.post_run()

    def _pre_epoch(self):
        pass

    def _post_epoch(self):
        need = (self.wandb or self._tb_writer is not None) and runtime.is_root()
        if need:
            metrics = {name: self.tracker[name][-1] for name in self.tracker if self.tracker[name]}
            if self.wandb:
                wandb.log(metrics)
            if self._tb_writer is not None:
                # the stage's _reduce_metrics has already advanced the
                # tracker, so the just-completed epoch is epoch - 1
                self._tb_writer.log_epoch(metrics, epoch=self.tracker.epoch - 1)

    def _teardown(self, exc: BaseException | None) -> None:
        """Guaranteed teardown — runs whether the stages finished, raised, or
        were interrupted; the exception (if any) propagates afterwards."""
        if isinstance(exc, KeyboardInterrupt):
            self.logger.info("=== run aborted by user (KeyboardInterrupt) ===")
        elif exc is not None:
            self.logger.error("=== run failed; traceback follows ===", exc_info=exc)
        if exc is not None:
            # the failure's requeue verdict (first-writer-wins: a preemption
            # or hang verdict already written this run is not stomped)
            requeue, kind, reason = self._classify_failure(exc)
            self._write_requeue_verdict(requeue, kind, reason)
        try:
            self._disarm_telemetry(exc)
        except Exception:
            self.logger.warning("telemetry teardown failed", exc_info=True)
        if self.checkpoint_dir is not None:
            # a failed/interrupted run may still have an async save in
            # flight: let it commit (or surface its own error to the log)
            # rather than orphan a half-written checkpoint behind the
            # exception that is about to propagate
            try:
                self.checkpoint_dir.wait_until_finished()
            except Exception:
                self.logger.warning("pending async checkpoint save failed during teardown", exc_info=True)
        if self.wandb and wandb_is_initialized():
            wandb.finish(exit_code=0 if exc is None else 1)
        if self._tb_writer is not None:
            self._tb_writer.close()
            self._tb_writer = None
        if self.io_redirector is not None:
            self.io_redirector.uninstall()
        # restore process-wide signal dispositions: a stale handler would
        # make post-run SIGTERM a silent no-op and pin this pipeline alive
        self._preemption.uninstall()


@contextmanager
def _run_guard(pipeline: TrainingPipeline):
    try:
        yield
    except BaseException as exc:
        pipeline._teardown(exc)
        raise
    else:
        pipeline._teardown(None)
