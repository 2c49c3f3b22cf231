"""Stage: one phase of an experiment; TrainValStage: the opinionated train loop.

Capability parity with /root/reference/dmlcloud/stage.py — the same hook set
(``pre_stage/post_stage/pre_epoch/post_epoch`` :81-105), epoch loop (:132-143),
metric prefix proxying (:59-76), early stop (:78-79), progress table
(:147,188-205), auto-metrics (:305-314), and barrier placement (:156,161) —
with the hot loop re-designed for XLA:

- The reference's per-batch sequence zero_grad -> step -> backward -> clip ->
  optimizer.step (:298-314, with DDP allreduce firing inside backward) becomes
  ONE jitted, donated, sharded function: value_and_grad + global-norm clip +
  optax update. The gradient mean over the ``data``/``fsdp`` axes is inserted
  by XLA as a fused allreduce over ICI — there is no hook machinery.
- State flows through a ``TrainState`` pytree (train_state.py) instead of
  in-place module mutation; the user's ``step(state, batch)`` is a pure
  function traced once.
- Per-step metrics returned by the step stay on device; tracking them never
  forces a host sync (metrics.py) — the dispatch queue stays full.
- Step timing is reported honestly under async dispatch:
  ``misc/step_dispatch_ms`` is host dispatch-to-dispatch time, and
  ``misc/train_step_avg_ms`` is the wall-clock per-step average taken after
  a single ``block_until_ready`` closes the pipeline at epoch end.

The **overlap engine** (doc/performance.md §"Overlap engine") removes the
remaining host-induced stalls, each behind a flag so behavior can be
bisected:

- ``async_checkpoint()`` (default True): Orbax saves commit on a background
  writer; at most one save is in flight (a new save first waits for the
  previous), with hard barriers at stage end, run end, and preemption exit.
- ``prefetch_depth()`` (default 2, the old ``device_prefetch``) +
  ``host_prefetch()``: double-buffered H2D transfer, optionally with host
  batch prep on a background thread (data/device.py).
- ``deferred_metrics()`` (default True): nothing inside the step loop reads
  a device value; host syncs happen only at ``log_every()`` boundaries
  (where the NaN/inf guard piggybacks on a 2-step-trailing loss fetch) and
  at the epoch-end fused exchange. ``deferred_metrics() == False`` restores
  the eager per-step readback for A/B bisection.
- Every host block is accounted: ``misc/host_stall_ms`` is the wall-clock
  the loop spent waiting on the device or on checkpoint commits this epoch.
"""

from __future__ import annotations

import sys
import time
from datetime import datetime
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import MetricTracker, Reduction
from .parallel import mesh as mesh_lib
from .parallel import runtime
from .parallel.runtime import is_root
from .telemetry import journal as _journal
from .train_state import TrainState
from .utils.logging import DevNullIO, flush_log_handlers
from .utils.profiling import StallTimer
from .utils.table import ProgressTable

__all__ = ["Stage", "TrainValStage", "DatasetNotFoundError"]


class DatasetNotFoundError(ValueError):
    """A stage asked the pipeline registry for a dataset that was never
    registered. ``val_epoch`` treats exactly this as "validation is optional"
    — a plain ``ValueError`` raised by a user ``val_dataset()`` override is a
    bug and propagates."""


class Stage:
    """One phase of training (pretrain / finetune / eval ...), run sequentially
    by the pipeline. Hook points: ``pre_stage``, ``post_stage``, ``pre_epoch``,
    ``post_epoch``. Parity: reference stage.py:18-220.
    """

    def __init__(self):
        self.pipeline = None  # set by the pipeline
        self.max_epochs = None  # set by the pipeline
        self.name = None  # set by the pipeline

        self.start_time = None
        self.stop_time = None
        self.epoch_start_time = None
        self.epoch_stop_time = None
        self.current_epoch = 1
        self._stop_requested = False
        self._preempt_exit = False

        self.metric_prefix = None
        self.table = None
        self.barrier_timeout = None
        self._stage_span_t0 = 0.0
        self._epoch_span_t0 = 0.0

    # -- conveniences -------------------------------------------------------
    @property
    def tracker(self) -> MetricTracker:
        return self.pipeline.tracker

    @property
    def logger(self):
        return self.pipeline.logger

    @property
    def mesh(self):
        return self.pipeline.mesh

    @property
    def config(self):
        return self.pipeline.config

    # -- metric proxying (reference stage.py:59-76) -------------------------
    def track_reduce(
        self,
        name: str,
        value: Any,
        step: int | None = None,
        reduction: Reduction = Reduction.MEAN,
        dim: list[int] | None = None,
        reduce_globally: bool = True,
        prefixed: bool = True,
    ):
        if prefixed and self.metric_prefix:
            name = f"{self.metric_prefix}/{name}"
        self.pipeline.track_reduce(name, value, step, reduction, dim, reduce_globally)

    def track(self, name: str, value: Any, step: int | None = None, prefixed: bool = True):
        if prefixed and self.metric_prefix:
            name = f"{self.metric_prefix}/{name}"
        self.pipeline.track(name, value, step)

    def stop_stage(self):
        """Request the epoch loop to stop after the current epoch."""
        self._stop_requested = True

    # -- hooks --------------------------------------------------------------
    def pre_stage(self):
        """Executed before the stage starts. Register stage-specific models
        and datasets here."""

    def post_stage(self):
        """Executed after the stage finishes — cleanup, artifact saves."""

    def pre_epoch(self):
        """Executed before each epoch."""

    def post_epoch(self):
        """Executed after each epoch, after metrics have been reduced."""

    def run_epoch(self):
        """Run one epoch. Must be implemented by subclasses."""
        raise NotImplementedError()

    def table_columns(self) -> list[str | dict[str, Any]]:
        """Customise the progress-table columns; same contract as the
        reference (stage.py:113-130): strings, or dicts with 'name' and
        'metric' keys ('metric': None => manually updated)."""
        columns = [
            {"name": "Epoch", "metric": "misc/epoch"},
            {"name": "Time/Epoch", "metric": None},
        ]
        if self.max_epochs is not None:
            columns.append({"name": "ETA", "metric": None})
        return columns

    # -- lifecycle (reference stage.py:132-205) -----------------------------
    def run(self):
        """Run until ``max_epochs`` or ``stop_stage()``. A restored
        ``_stop_requested`` (stage already stopped before the interruption)
        skips the loop entirely."""
        self._pre_stage()
        _journal.setup("stage", self._stage_span_t0)
        while not self._stop_requested and (self.max_epochs is None or self.current_epoch <= self.max_epochs):
            self._pre_epoch()
            # the runtime sanitizer's guard window is exactly one epoch:
            # everything inside may not do unaccounted implicit transfers;
            # the epoch-end reduce below (_post_epoch) is outside on purpose
            with self._sanitizer_guard():
                self.run_epoch()
            if getattr(self, "_mid_epoch_exit", False):
                # a step-granular save already persisted the state and a
                # coordinated preemption cut the epoch short: exit WITHOUT
                # _post_epoch — the partial epoch must not reduce metrics
                # or be recorded as complete (resume continues inside it)
                self._preempt_exit = True
                self.logger.info(
                    f"preemption requested; stage {self.name!r} exiting cleanly mid-epoch "
                    f"{self.current_epoch} (state saved at the last step boundary; resumable)"
                )
                break
            # decide BEFORE _post_epoch so its checkpoint save treats this
            # epoch as final even under checkpoint_every() > 1
            self._preempt_exit = self.pipeline._preemption_coordinated()
            self._post_epoch()
            if self._preempt_exit:
                # clean early exit WITHOUT _stop_requested: the epoch's
                # checkpoint is saved and a requeued run resumes here
                self.logger.info(
                    f"preemption requested; stage {self.name!r} exiting cleanly after epoch "
                    f"{self.current_epoch - 1} (resumable)"
                )
                break
        self._post_stage()

    def _sanitizer_guard(self):
        """The pipeline sanitizer's epoch window, or a no-op when off."""
        san = getattr(self.pipeline, "_sanitizer", None)
        if san is not None and san.armed:
            return san.epoch_guard(stage=self.name or type(self).__name__)
        from contextlib import nullcontext

        return nullcontext()

    def _pre_stage(self):
        self.start_time = datetime.now()
        self._stage_span_t0 = _journal.now()
        # NOTE: root-only table — fixes the reference quirk of passing the
        # function `is_root` (always truthy) instead of calling it (stage.py:147).
        self.table = ProgressTable(file=sys.stdout if is_root() else DevNullIO())
        self._setup_table()
        if len(self.pipeline.stages) > 1:
            self.logger.info(f"\n========== STAGE: {self.name} ==========")
        t0 = _journal.now()
        self.pre_stage()
        _journal.setup("pre_stage", t0)
        flush_log_handlers(self.logger)
        self.pipeline.barrier(self.barrier_timeout)

    def _post_stage(self):
        self.table.close()
        self.post_stage()
        self.pipeline.barrier(self.barrier_timeout)
        self.stop_time = datetime.now()
        _journal.emit("stage", self._stage_span_t0, label=self.name, epochs=self.current_epoch - 1)
        if len(self.pipeline.stages) > 1:
            self.logger.info(f"Finished stage in {self.stop_time - self.start_time}")

    def _pre_epoch(self):
        self.epoch_start_time = datetime.now()
        self._epoch_span_t0 = _journal.now()
        self.table["Epoch"] = self.current_epoch
        self.pre_epoch()
        self.pipeline._pre_epoch()

    def _post_epoch(self):
        self.epoch_stop_time = datetime.now()
        _journal.emit("epoch", self._epoch_span_t0, label=self.name, epoch=self.current_epoch)
        self._reduce_metrics()
        self.post_epoch()
        self.pipeline._post_epoch()
        self._update_table()
        self.current_epoch += 1

    def _reduce_metrics(self):
        self.track(name="misc/epoch", value=self.current_epoch, prefixed=False)
        self.track(
            name="misc/epoch_time",
            value=(self.epoch_stop_time - self.epoch_start_time).total_seconds(),
            prefixed=False,
        )
        self.tracker.next_epoch()

    def _setup_table(self):
        for column_dct in self._metrics():
            column_dct = dict(column_dct)
            display_name = column_dct.pop("name")
            column_dct.pop("metric")
            self.table.add_column(display_name, **column_dct)

    def _update_table(self):
        self.table.update("Epoch", self.current_epoch)
        self.table.update("Time/Epoch", str((datetime.now() - self.start_time) / self.current_epoch).split(".")[0])
        if self.max_epochs is not None:
            eta = (datetime.now() - self.start_time) / self.current_epoch * (self.max_epochs - self.current_epoch)
            self.table.update("ETA", str(eta).split(".")[0])
        for column_dct in self._metrics():
            metric_name = column_dct["metric"]
            if metric_name is not None and metric_name in self.tracker:
                history = self.tracker[metric_name]
                if history:
                    self.table.update(column_dct["name"], history[-1])
        self.table.next_row()

    def _metrics(self):
        metrics = []
        for column in self.table_columns():
            if isinstance(column, str):
                metrics.append({"name": column, "metric": column})
            elif isinstance(column, dict):
                if "name" not in column:
                    raise ValueError('Column dict must contain a "name" key')
                if "metric" not in column:
                    raise ValueError('Column dict must contain a "metric" key')
                metrics.append(column)
            else:
                raise ValueError(f"Invalid column: {column}. Must be a string or a dict.")
        return metrics


class TrainValStage(Stage):
    """Opinionated train+val stage around ONE compiled, sharded step.

    Subclasses implement ``step(state, batch) -> loss`` or
    ``-> (loss, metrics_dict)`` as a *pure traced function* (the reference's
    imperative ``step(batch)``, stage.py:263-264, cannot exist under jit).
    The stage owns a ``TrainState`` built from the pipeline's registered
    model/optimizer in ``_pre_stage`` (override ``make_state`` to customise),
    compiles train/val steps once, and tracks the reference's auto-metrics:
    ``{train,val}/loss``, ``misc/total_{train,val}_batches`` (SUM, global),
    ``misc/worker_{train,val}_batches`` (SUM, local), and per-scheduler
    ``misc/lr_{name}``. The reference's ``misc/step_time_ms`` is
    DELIBERATELY renamed: under async dispatch the loop-body time is host
    enqueue cost, so it ships as ``misc/step_dispatch_ms``, with
    ``misc/train_step_avg_ms`` carrying the wall-clock per-step average.

    ``precision="int8"`` switches the compiled train step to quantized
    training (models/quant.py): master fp32 weights stay the params the
    optimizer, EMA shadow and checkpoints see, while INSIDE the step's
    loss closure every matrix kernel is wrapped as a
    :class:`~dmlcloud_tpu.models.quant.QuantTrainTensor` — int8 matmuls on
    the forward and input-gradient paths, full-precision weight grads
    (straight-through), per-channel scales DELAYED one step via the amax
    tree carried in ``state.extras[QUANT_AMAX_KEY]`` and refreshed from
    the post-update params. Validation always runs full precision on the
    master weights.
    """

    def __init__(self, precision: str = "full"):
        super().__init__()
        if precision not in ("full", "int8"):
            raise ValueError(f'precision must be "full" or "int8", got {precision!r}')
        self._precision = str(precision)
        self.is_train = True
        self.state: TrainState | None = None
        self._policy: Any = "replicate"
        self._train_step_fn = None
        self._val_step_fn = None
        #: what the model's rematerialised blocks kept when the train step was last traced
        #: (``models/transformer.RematRoom.plan``; the precompile log line), None without remat
        self._remat_plan: dict | None = None
        #: batches of the CURRENT epoch to skip on a mid-epoch resume
        #: (one-shot, set by _restore_state from a step-save sidecar,
        #: already scaled to THIS run's world size)
        self._resume_skip_steps = 0
        #: the train DataPipeline's saved iterator state, when the sidecar
        #: carries one (one-shot; preferred over the raw batch skip)
        self._resume_data_state = None
        #: wall-clock of the most recent state save — the preemption
        #: verdict's save-on-preempt latency
        self._last_save_latency_s: float | None = None
        #: set when a preemption poll at a step-save point cut the epoch
        #: short: run_epoch skips val and Stage.run exits without treating
        #: the partial epoch as complete
        self._mid_epoch_exit = False
        #: accumulates the wall-clock the host spends blocked on the device
        #: or on checkpoint commits; reset per epoch, published as
        #: ``misc/host_stall_ms``
        self._stall = StallTimer()
        #: cold-start machinery (compile/): signature registries wrapping the
        #: jitted steps when precompile()/buckets() are armed, else None —
        #: the default path keeps the raw jit fns with zero added overhead
        self._train_compiled = None
        self._val_compiled = None
        self._buckets_resolved: tuple[int, ...] | None = None
        #: True exactly while the per-batch body of train_epoch runs — the
        #: window in which NO device readback may happen under
        #: ``deferred_metrics()`` (tests assert against it)
        self._in_step_loop = False
        #: telemetry (flight recorder) accounting: host ns spent blocked in
        #: the feed iterator's next() this epoch (the goodput ledger's
        #: data_wait bucket), and the cached cost-analysis FLOPs fallback
        #: for MFU when step_flops() is not declared
        self._gp_data_wait_ns = 0
        self._cost_flops: float | None = None
        #: padding accounting over this epoch's HOST batches (telemetry
        #: only): slots whose ``segment_ids`` mark padding vs all token
        #: slots — ``misc/pad_fraction``, the signal the goodput advisor
        #: reads (doc/data.md)
        self._gp_pad_slots = 0
        self._gp_token_slots = 0

    # -- overridables (parity: reference stage.py:228-257) ------------------
    def train_dataset(self):
        ds = self.pipeline.datasets.get("train")
        if ds is None:
            raise DatasetNotFoundError(
                'No "train" dataset found in pipeline. Use register_dataset("train", ...) to register a dataset.'
            )
        return ds

    def val_dataset(self):
        ds = self.pipeline.datasets.get("val")
        if ds is None:
            raise DatasetNotFoundError(
                'No "val" dataset found in pipeline. Use register_dataset("val", ...) to register a dataset.'
            )
        return ds

    def loss_metric_name(self) -> str:
        return "loss"

    def train_metric_prefix(self) -> str:
        return "train"

    def val_metric_prefix(self) -> str:
        return "val"

    def gradient_clip(self) -> float:
        """Global-norm clip threshold; 0 disables (reference stage.py:256-257)."""
        return 0.0

    def precision(self) -> str:
        """Matmul precision of the compiled TRAIN step: ``"full"`` (the
        model's own dtype) or ``"int8"`` (quantized training — see the
        class docstring and models/quant.py). A knob method like its
        neighbours so subclasses may override instead of passing the
        constructor arg."""
        return self._precision

    def gradient_accumulation(self) -> int:
        """Number of microbatches to accumulate per optimizer step (1
        disables). The registered batch is split along its leading axis and
        scanned with ``lax.scan`` INSIDE the one compiled step — grads and
        metrics accumulate in fp32 on device, the optimizer applies once.
        Losses, grads, AND step metrics are AVERAGED over microbatches, so
        equivalence with the unaccumulated step requires ``step`` to return
        mean-reduced values: a sum-reduced loss would be rescaled by
        1/accum, and a count-style metric (e.g. samples seen) silently
        changes scale by 1/accum — derive counts from the batch size
        outside ``step`` instead.
        This is the TPU shape of large effective batches under a tight HBM
        budget: one trace, one dispatch, no host round trips per microbatch.
        (The reference has no equivalent; its imperative loop would pay
        ``accum`` Python dispatches, stage.py:290-314.)"""
        return 1

    def ema_decay(self) -> float:
        """Per-step decay of an exponential moving average of the params,
        kept as a fp32 shadow tree on the state (same shapes and shardings
        as the params) and updated inside the one compiled train step; 0
        disables, typical values are 0.999-0.9999. Validation runs on the
        averaged params (see ``val_with_ema``), and the shadow rides
        checkpoints and resume like every other state leaf.

        The reference has no equivalent; torch users bolt on
        ``swa_utils.AveragedModel``, which costs a separate full-model pass
        per update on host-dispatched kernels."""
        return 0.0

    def val_with_ema(self) -> bool:
        """Whether validation sees the EMA params instead of the raw ones
        (only meaningful when ``ema_decay() > 0``; default True — evaluating
        the average is the point of keeping it)."""
        return True

    def step_flops(self) -> float:
        """Total FLOPs one optimizer step performs across the WHOLE mesh
        (forward+backward for the global batch; multiply-add counts as 2 —
        the convention hardware peaks use). Return a positive number and the
        stage tracks ``misc/mfu`` each epoch from the measured per-step
        wall clock and the mesh's aggregate chip peak
        (``utils.profiling.peak_flops_for_kind``). 0 (default) disables; on
        backends whose device kind has no entry in the bf16 peak table
        (CPU/GPU dev runs) the metric is skipped rather than computed
        against a made-up peak.

        Rules of thumb: transformer training ≈ ``6 * params * tokens_per_
        batch`` (PaLM convention, embedding lookups excluded); ResNet-50 @
        224² ≈ ``24.6e9 * images_per_batch`` (forward and backward, a
        multiply-add counted as two operations)."""
        return 0.0

    def model_name(self) -> str | None:
        """Which registered model this stage trains (None = the only one)."""
        return None

    def device_prefetch(self) -> int:
        """Batches kept in flight on device ahead of the compiled step (the
        default feeding path runs every dataset through
        ``data.device_iterator``, overlapping host->HBM transfers with
        compute). Return 0 to feed synchronously (one ``make_global_batch``
        per step) — e.g. when batches are huge and HBM is tight."""
        return 2

    def prefetch_depth(self) -> int:
        """The overlap engine's canonical name for the device prefetch depth
        (default: whatever ``device_prefetch()`` says, so existing overrides
        keep working). 2 = double buffering — batch N+1's H2D copy runs
        while the device computes batch N; 0 = synchronous per-step puts."""
        return int(self.device_prefetch())

    def host_prefetch(self) -> int:
        """Host batches prepared ahead on a background thread before the
        device transfer queue (data/device.py). 0 (default) keeps host batch
        prep on the training thread — raise it when prep (augmentation,
        decode, disk reads) is a measurable share of the step budget."""
        return 0

    def precompile(self) -> bool:
        """Whether to AOT-compile the train/val steps at stage start (the
        ``jit(...).lower(...).compile()`` pattern over abstract
        ``ShapeDtypeStruct``\\ s, compile/aot.py): compile cost lands in a
        timed precompile phase BEFORE the data loop (``misc/compile_ms``),
        and sharding/shape mismatches error at stage start instead of
        step 1. The batch signature comes from ``batch_spec()`` or, by
        default, from peeking the first batch's shapes/dtypes (one
        signature per bucket when ``buckets()`` is set). Default: the
        pipeline's ``precompile=`` flag (False)."""
        return bool(getattr(self.pipeline, "_precompile", False))

    def buckets(self):
        """Batch-dim bucket sizes for ragged batches, ascending (e.g.
        ``(8, 32, 128)`` with 128 the full batch size), or None to disable.
        Every host batch is padded up to the smallest fitting bucket before
        the device transfer — mapping batches gain a zero-weight
        ``bucket_mask_key()`` leaf (reduce per-sample losses with
        ``compile.masked_mean`` to keep the math identical) — so the
        compiled-signature count is bounded by ``len(buckets)`` instead of
        growing with the data (``misc/recompiles`` tracks growth events per
        epoch). Default: the pipeline's ``buckets=`` flag (None)."""
        return getattr(self.pipeline, "_buckets", None)

    def bucket_mask_key(self) -> str:
        """Key under which bucketing injects the padding mask into mapping
        batches (1.0 real row / 0.0 padded row)."""
        from .compile.buckets import DEFAULT_MASK_KEY

        return DEFAULT_MASK_KEY

    def batch_spec(self):
        """Declared abstract spec of one HOST train batch (a pytree of
        ``jax.ShapeDtypeStruct`` — or of example arrays — matching what the
        train dataset yields, pre-sharding). None (default) peeks the first
        batch instead; declare it when the dataset is a one-shot iterator or
        when you want stage-start validation against an explicit contract."""
        return None

    def async_checkpoint(self) -> bool:
        """Whether this stage's Orbax scopes commit saves on a background
        writer (non-blocking saves; default True). The loop never has more
        than one save in flight — a new save first waits out the previous —
        and hard barriers at stage end / run end / preemption exit guarantee
        everything is committed before the process goes away, so resume
        semantics are identical to synchronous saves: a checkpoint either
        committed completely or does not exist. False restores fully
        synchronous saves (the bisection baseline)."""
        return True

    def deferred_metrics(self) -> bool:
        """Whether per-step metrics stay on device until a sync point
        (default True): no ``.item()``/``device_get`` runs inside the step
        loop; host syncs happen only every ``log_every()`` steps (a 2-step-
        trailing loss fetch that also feeds the NaN/inf guard and the live
        table) and at the epoch-end fused exchange. False restores the
        eager path — every step's metrics are fetched to host immediately —
        which produces identical epoch-end values, just slower."""
        return True

    def log_every(self) -> int:
        """Steps between host syncs inside the training loop when
        ``deferred_metrics()`` is on: each boundary fetches one trailing
        loss value (already computed — minimal stall), updates the live
        console EMA, and runs the NaN/inf guard. 0 disables the periodic
        sync entirely (the guard then only sees the epoch-end values)."""
        return 50

    def nan_guard(self) -> bool:
        """Whether the periodic ``log_every()`` sync raises
        ``FloatingPointError`` on a non-finite loss (default True). Under
        deferred metrics the check piggybacks on the boundary fetch —
        detection trails the bad step by up to ``log_every()`` steps instead
        of paying a per-step sync; with eager metrics it checks every step."""
        return True

    def checkpoint_every(self) -> int:
        """Epochs between automatic TrainState saves (0 disables). Active
        only when ``pipeline.enable_checkpointing()`` was called. The
        reference leaves tensor state to user hooks (SURVEY.md §3.5); here a
        resumed pipeline continues bit-for-bit: params, optimizer state, rng,
        extras, metric histories, and the epoch counter are all restored."""
        return 1

    def checkpoint_every_steps(self) -> int:
        """Steps between mid-epoch state saves: every N steps the full
        TrainState is saved collectively (separate Orbax scope keyed by the
        global optimizer step, newest-only retention), the preemption flag
        is polled so a preempted run exits within N steps instead of at the
        epoch boundary, and a resume whose step save is fresher than the
        last completed epoch continues MID-epoch by fast-forwarding the
        train dataset past the consumed batches. 0 disables (the default).

        Epoch-boundary checkpointing (``checkpoint_every``) loses the whole
        current epoch on a crash or preemption — unacceptable when one
        "epoch" is hours of LM pretraining. Mid-epoch resume requires
        per-epoch deterministic iteration order (true for every pipeline
        here, which seeds shuffles by epoch), and continues bit-for-bit.

        Metrics caveat: the resumed epoch's tracked metrics cover only the
        post-resume steps (partial reducer buffers are not checkpointed);
        counters like ``misc/total_train_batches`` under-count that epoch."""
        return 0

    def checkpoint_keep(self) -> int:
        """How many checkpoints the stage's Orbax manager retains."""
        return 3

    def checkpoint_best_metric(self) -> str | None:
        """Tracker metric (e.g. ``'val/loss'``) ranking which checkpoints to
        KEEP: retention holds the best ``checkpoint_keep()`` by this metric
        instead of the most recent. None (default) keeps most-recent.
        Orbax additionally always preserves the newest checkpoint, so a
        Slurm-requeue resume continues from the latest epoch either way."""
        return None

    def checkpoint_best_mode(self) -> str:
        """'min' (e.g. losses) or 'max' (e.g. accuracies)."""
        return "min"

    # -- state construction -------------------------------------------------
    def make_state(self) -> TrainState:
        """Build the TrainState from the pipeline registries. Override for
        multi-model setups.

        Registry arrays are COPIED into the state: the compiled step donates
        its input state, and on the first call those buffers would otherwise
        be the registry's own arrays — a later stage (or user code reading
        ``pipeline.models`` after the run) would see deleted buffers. The rng
        is folded per stage so stages draw independent streams."""
        entry = self.pipeline._model_entry(self.model_name())
        tx = self.pipeline._optimizer_for(entry.name)

        def fresh(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree
            )

        stage_index = self.pipeline.stages.index(self) if self in self.pipeline.stages else 0
        params = fresh(entry.params)
        extras = fresh(entry.extras) if entry.extras is not None else None
        if self.precision() == "int8":
            # seed the delayed-scale state: step 0 quantizes with the
            # INITIAL params' amax (models/quant.py — every later step
            # uses the previous step's post-update statistics)
            from .models.quant import QUANT_AMAX_KEY, amax_tree

            extras = dict(extras or {})
            extras[QUANT_AMAX_KEY] = amax_tree(params)
        return TrainState.create(
            apply_fn=entry.apply_fn,
            params=params,
            tx=tx,
            rng=jax.random.fold_in(self.pipeline.root_key, stage_index),
            extras=extras,
            ema=True if float(self.ema_decay()) > 0.0 else None,
            mesh=self.mesh,
            policy=entry.policy,
        )

    # -- the pure step ------------------------------------------------------
    def step(self, state: TrainState, batch) -> Any:
        """Pure traced step: return ``loss`` or ``(loss, metrics_dict)``.
        Runs under jit — no Python side effects, no host sync."""
        raise NotImplementedError()

    def train_step(self, state, batch):
        return self.step(state, batch)

    def val_step(self, state, batch):
        return self.step(state, batch)

    # -- compiled steps -----------------------------------------------------
    def _build_train_step(self) -> Callable:
        clip = float(self.gradient_clip())
        accum = int(self.gradient_accumulation())
        ema_decay = float(self.ema_decay())
        int8 = self.precision() == "int8"
        if int8:
            from .models.quant import QUANT_AMAX_KEY, amax_tree, wrap_train_tree
        from .models.transformer import RematRoom, remat_room

        device = self.mesh.local_devices[0]

        def train_step(state: TrainState, batch):
            rng = jax.random.fold_in(state.rng, state.step)

            def loss_fn(params, extras, rng, mb):
                if int8:
                    # wrap INSIDE the differentiated closure: grads keep
                    # the plain-params structure, the user's step sees
                    # QuantTrainTensor kernels the QuantDense layers
                    # dispatch on (models/quant.py), and the delayed
                    # scales ride in from the previous step's extras
                    params = wrap_train_tree(params, extras[QUANT_AMAX_KEY])
                out = self.train_step(state.replace(params=params, extras=extras, rng=rng), mb)
                # step may return loss | (loss, metrics) | (loss, metrics, new_extras)
                if not isinstance(out, tuple):
                    loss, metrics, new_extras = out, {}, extras
                elif len(out) == 2:
                    (loss, metrics), new_extras = out, extras
                else:
                    loss, metrics, new_extras = out
                return loss, (metrics, new_extras)

            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            # what a rematerialised model may keep for the backward: the device's room, read as the step is traced
            room = RematRoom.of(device)
            with remat_room(room):
                if accum == 1:
                    (loss, (metrics, new_extras)), grads = grad_fn(state.params, state.extras, rng, batch)
                else:
                    loss, metrics, new_extras, grads = self._accumulate(grad_fn, state, rng, batch, accum)
            if room.plan is not None:
                self._remat_plan = room.plan
                t = _journal.now()
                _journal.emit("remat", t, t, label=self.name, **room.plan)
            # the two scopes are phases of the step's profile (utils/profiling.PHASES)
            if clip > 0.0:
                with jax.named_scope("grad_clip"):
                    gnorm = jax.lax.rsqrt(
                        jnp.maximum(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree_util.tree_leaves(grads)), 1e-12)
                    )
                    scale = jnp.minimum(1.0, clip * gnorm)
                    grads = jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads)
            with jax.named_scope("optimizer"):
                new_state = state.apply_gradients(grads).replace(extras=new_extras)
            if int8:
                # delayed scaling: the NEXT step quantizes with THIS
                # step's post-update amax — one fused reduction here, no
                # statistics pass on the forward's critical path
                new_state = new_state.replace(
                    extras={**new_state.extras, QUANT_AMAX_KEY: amax_tree(new_state.params)}
                )
            if ema_decay > 0.0:
                with jax.named_scope("optimizer"):
                    new_state = new_state.update_ema(ema_decay)
            metrics = dict(metrics)
            metrics[self.loss_metric_name()] = loss
            return new_state, metrics

        state_sh = self.state.shardings(self.mesh, self._policy)
        batch_sh = None  # inferred from the (already sharded) batch arrays
        return jax.jit(
            train_step,
            donate_argnums=0,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
        )

    @staticmethod
    def _accumulate(grad_fn, state, rng, batch, accum):
        """Traced microbatch accumulation: split ``batch`` [B, ...] into
        ``accum`` slices of B/accum and ``lax.scan`` ``grad_fn`` over them.
        Losses, metrics, and grads accumulate in fp32 (grads cast back to
        the param dtype for the optimizer); auxiliary state (``extras``,
        e.g. BatchNorm stats) threads through the scan so the last
        microbatch's update wins, exactly as sequential steps would."""
        leaves = jax.tree_util.tree_leaves(batch)
        for leaf in leaves:
            if leaf.shape[0] % accum:
                raise ValueError(
                    f"gradient_accumulation()={accum} must divide the batch dimension, got {leaf.shape[0]}"
                )
        micro = jax.tree_util.tree_map(lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch)

        # One eval_shape reveals the metrics pytree so the fp32 accumulators
        # can be preallocated for the scan carry.
        first = jax.tree_util.tree_map(lambda x: x[0], micro)
        out_shape = jax.eval_shape(grad_fn, state.params, state.extras, rng, first)
        metrics_shape = out_shape[0][1][0]

        def f32_zeros(tree):
            return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, jnp.float32), tree)

        init = (
            f32_zeros(state.params),  # grad accumulators
            state.extras,
            jnp.zeros((), jnp.float32),  # loss
            f32_zeros(metrics_shape),
        )

        def body(carry, xs):
            grads_acc, extras, loss_acc, metrics_acc = carry
            i, mb = xs
            (loss, (metrics, new_extras)), grads = grad_fn(state.params, extras, jax.random.fold_in(rng, i), mb)
            grads_acc = jax.tree_util.tree_map(lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
            metrics_acc = jax.tree_util.tree_map(lambda a, m: a + m.astype(jnp.float32), metrics_acc, metrics)
            return (grads_acc, new_extras, loss_acc + loss.astype(jnp.float32), metrics_acc), None

        (grads_acc, extras, loss_acc, metrics_acc), _ = jax.lax.scan(
            body, init, (jnp.arange(accum), micro)
        )
        grads = jax.tree_util.tree_map(
            lambda g, p: (g / accum).astype(p.dtype), grads_acc, state.params
        )
        metrics = jax.tree_util.tree_map(lambda m: m / accum, metrics_acc)
        return loss_acc / accum, metrics, extras, grads

    def _build_val_step(self) -> Callable:
        use_ema = float(self.ema_decay()) > 0.0 and self.val_with_ema()

        def val_step(state: TrainState, batch):
            if use_ema:
                # evaluate the averaged weights: the user's val_step reads
                # state.params as usual and sees the EMA tree, cast to the
                # params' dtypes (the fp32 shadow must not silently promote
                # a bf16 model's whole forward pass to fp32)
                ema = jax.tree_util.tree_map(
                    lambda e, p: e.astype(p.dtype), state.ema, state.params
                )
                state = state.replace(params=ema)
            out = self.val_step(state, batch)
            # same contract as train: loss | (loss, metrics) | (loss, metrics, extras);
            # extras are discarded in eval (no state update).
            if not isinstance(out, tuple):
                loss, metrics = out, {}
            else:
                loss, metrics = out[0], out[1]
            metrics = dict(metrics)
            metrics[self.loss_metric_name()] = loss
            return metrics

        return jax.jit(val_step)

    # -- lifecycle ----------------------------------------------------------
    def _configure_state_manager(self):
        """Bind this stage's Orbax retention options (keep count, optional
        keep-best ranking) at first manager creation — before any
        save/restore touches the scope."""
        ckpt = self.pipeline.checkpoint_dir
        if ckpt is None:
            return
        asave = bool(self.async_checkpoint())
        # step-save scope first: it must get its newest-only retention even
        # when the user pre-configured the EPOCH scope (early return below)
        # or disabled epoch checkpointing outright
        if int(self.checkpoint_every_steps()) > 0 and not ckpt.has_state_manager(self._steps_scope):
            # crash/preemption insurance only — history lives in epoch saves
            ckpt.state_manager(self._steps_scope, max_to_keep=1, async_save=asave)
        if int(self.checkpoint_every()) <= 0:
            return
        if ckpt.has_state_manager(self.name):
            return  # the user configured this scope in pre_stage; their options win
        opts = {}
        metric = self.checkpoint_best_metric()
        if metric is not None:
            mode = self.checkpoint_best_mode()
            if mode not in ("min", "max"):
                raise ValueError(f"checkpoint_best_mode() must be 'min' or 'max', got {mode!r}")
            from orbax.checkpoint import checkpoint_managers as ocm

            # best-N by the metric PLUS always the newest (deterministic
            # requeue-resume freshness; best_fn+max_to_keep alone leaves the
            # latest checkpoint's survival to async-gc timing)
            opts = {
                "preservation_policy": ocm.AnyPreservationPolicy(
                    [
                        ocm.LatestN(n=1),
                        ocm.BestN(
                            get_metric_fn=lambda m: m[metric],
                            reverse=(mode == "min"),
                            n=int(self.checkpoint_keep()),
                            # metricless saves must not accumulate forever;
                            # LatestN above still protects the newest one
                            keep_checkpoints_without_metrics=False,
                        ),
                    ]
                )
            }
        keep = None if opts else int(self.checkpoint_keep())  # policy owns retention when set
        ckpt.state_manager(self.name, max_to_keep=keep, async_save=asave, **opts)

    @property
    def _steps_scope(self) -> str:
        """Orbax scope for mid-epoch step saves (separate from the
        epoch-keyed scope so step ids never collide with epoch numbers)."""
        return f"{self.name}.steps"

    def _pre_stage(self):
        super()._pre_stage()
        if self.state is None:
            entry = self.pipeline._model_entry(self.model_name())
            self._policy = entry.policy
            t0 = _journal.now()
            self.state = self.make_state()
            _journal.setup("state", t0)
        self._configure_state_manager()
        if self.pipeline.resumed and (
            int(self.checkpoint_every()) > 0 or int(self.checkpoint_every_steps()) > 0
        ):
            # manual mode (checkpoint_every()==0) owns its restore layout too
            t0 = _journal.now()
            self._restore_state()
            _journal.setup("restore", t0)
        self._train_step_fn = self._build_train_step()
        self._val_step_fn = self._build_val_step()
        self._setup_compiled_steps()
        san = getattr(self.pipeline, "_sanitizer", None)
        if san is not None and san.armed:
            # the sanitizer's dispatch probe (host-numpy leaves == implicit
            # H2D) interposes OUTSIDE TraceGuard/PrecompiledStep so the
            # default path gains zero overhead when sanitize is off
            self._train_step_fn = san.wrap_dispatch(self._train_step_fn, where=f"{self.name}.train_step")
            self._val_step_fn = san.wrap_dispatch(self._val_step_fn, where=f"{self.name}.val_step")

    # -- cold-start machinery (compile/; doc/performance.md §4) -------------
    def _setup_compiled_steps(self):
        """Arm the signature registries and (optionally) the AOT precompile
        phase. Inactive (raw jit fns, zero added per-step cost) unless
        ``precompile()`` or ``buckets()`` says otherwise."""
        raw_buckets = self.buckets()
        if raw_buckets:
            from .compile.buckets import resolve_buckets

            self._buckets_resolved = resolve_buckets(raw_buckets)
        else:
            self._buckets_resolved = None
        if not self.precompile() and self._buckets_resolved is None:
            return
        from .compile.aot import PrecompiledStep
        from .lint import TraceGuard

        self._train_compiled = PrecompiledStep(self._train_step_fn, name=f"{self.name}.train_step")
        self._val_compiled = PrecompiledStep(self._val_step_fn, name=f"{self.name}.val_step")
        if self.precompile():
            self._run_precompile_phase()
        # the runtime retrace guard reads the registry's _cache_size(): any
        # signature beyond the expected bucket set is a mid-run compile stall
        expected = len(self._buckets_resolved) if self._buckets_resolved else 1
        self._train_step_fn = TraceGuard(
            self._train_compiled, max_traces=expected, action="warn", name=f"{self.name}.train_step"
        )
        self._val_step_fn = self._val_compiled

    def _host_batch_spec(self, dataset_fn) -> Any:
        """The abstract HOST batch for precompilation: ``batch_spec()`` if
        declared (train only), else the peeked first batch; None when the
        dataset is absent."""
        if dataset_fn == self.train_dataset:
            declared = self.batch_spec()
            if declared is not None:
                from .compile.aot import abstract_spec

                return abstract_spec(declared)
        try:
            ds = dataset_fn()
        except DatasetNotFoundError:
            return None
        if iter(ds) is ds:
            raise ValueError(
                f"precompile() needs the first batch's shapes, but stage {self.name!r} "
                "feeds from a one-shot iterator that peeking would consume — declare "
                "batch_spec() or register a re-iterable dataset"
            )
        from .data.device import peek_spec

        spec, _ = peek_spec(ds)
        return spec

    def _run_precompile_phase(self):
        """The timed precompile phase: lower+compile every expected train/val
        signature against abstract specs BEFORE the data loop, so compile
        cost is measured (``misc/compile_ms``), cache hits are counted, and
        sharding/shape mismatches fail here — at stage start."""
        from .compile import aot
        from .compile import cache as compile_cache
        from .compile.buckets import bucket_spec
        from .ops import hyper_connection

        stats0 = compile_cache.cache_stats()
        state_spec = aot.abstract_spec(self.state)

        def global_specs(host_spec):
            if host_spec is None:
                return []
            if self._buckets_resolved:
                host_variants = [
                    bucket_spec(host_spec, b, mask_key=self.bucket_mask_key())
                    for b in self._buckets_resolved
                ]
            else:
                host_variants = [host_spec]
            out = []
            for hs in host_variants:
                gs = aot.global_batch_spec(hs, self.mesh)
                aot.validate_global_batch_spec(gs, self.mesh)
                out.append(gs)
            return out

        n_train = 0
        elapsed_ms = 0.0  # the compile spans' own readings (PrecompiledStep.precompile)
        verify_args: list[tuple] = []
        with hyper_connection.recording() as mhc_seen:  # the path each hyper-connection's maps took, for the log line
            for gs in global_specs(self._host_batch_spec(self.train_dataset)):
                elapsed_ms += self._train_compiled.precompile(state_spec, gs)
                verify_args.append(("train_step", self._train_compiled, (state_spec, gs), (0,)))
                n_train += 1
        # val is best-effort: a stage may have no val dataset, or one whose
        # first-batch peek is impossible — the val step then compiles lazily
        n_val = 0
        try:
            for gs in global_specs(self._host_batch_spec(self.val_dataset)):
                elapsed_ms += self._val_compiled.precompile(state_spec, gs)
                verify_args.append(("val_step", self._val_compiled, (state_spec, gs), ()))
                n_val += 1
        except ValueError as e:
            self.logger.warning(f"val-step precompile skipped: {e}")

        if not ("misc/compile_ms" in self.tracker and self.tracker.has_value("misc/compile_ms")):
            self.track("misc/compile_ms", round(elapsed_ms, 3), prefixed=False)
        stats1 = compile_cache.cache_stats()
        if n_train or n_val:
            plan = self._remat_plan
            kept = "" if plan is None else (
                f"; remat: {plan['kept_blocks']} of {plan['blocks']} blocks keep their MLP's products, "
                f"{plan['kept_bytes']} bytes (free {plan['free_bytes']}, reserve {plan['reserve_bytes']})"
            )
            if mhc_seen:
                kept += f"; mhc: {hyper_connection.summary(mhc_seen)}"
            self.logger.info(
                f"precompile: {n_train} train + {n_val} val signature(s) in {elapsed_ms:.0f} ms "
                f"(compile cache: {stats1['aot_hits'] - stats0['aot_hits']} hit(s), "
                f"{stats1['aot_misses'] - stats0['aot_misses']} miss(es)){kept}"
            )
        else:
            self.logger.warning(
                f"precompile() on stage {self.name!r} found no batch spec to compile "
                "against; the first step pays the compile as usual"
            )
        self._verify_precompiled(verify_args)

    def _verify_precompiled(self, verify_args: list[tuple]) -> None:
        """The ``TrainingPipeline(verify=...)`` arm: audit every executable
        the precompile phase just built with the IR verifier (doc/lint.md
        DML6xx) BEFORE the data loop. Re-uses the compiled artifacts — the
        preflight adds jaxpr traces (cheap, no XLA) but zero compiles."""
        mode = getattr(self.pipeline, "_verify_mode", None)
        if not mode or not verify_args:
            return
        from .compile import aot
        from .lint import LintError
        from .lint import ir as ir_mod

        budget = getattr(self.pipeline, "_hbm_budget", None)
        specs = []
        for step_name, reg, args, donate in verify_args:
            specs.append(
                ir_mod.ProgramSpec(
                    name=f"{self.name}.{step_name}[{len(specs)}]",
                    fn=reg._fn,
                    args=args,
                    donate_argnums=donate,
                    mesh=self.mesh,
                    hbm_budget_bytes=budget,
                    kind="train",
                    compiled=reg._compiled.get(aot.signature_of(args)),
                )
            )
        t0 = time.perf_counter()
        findings = ir_mod.verify_programs(specs)
        elapsed_ms = (time.perf_counter() - t0) * 1e3
        self.pipeline.verify_findings = list(findings)
        self.logger.info(
            f"verify: {len(findings)} finding(s) over {len(specs)} precompiled "
            f"program(s) in {elapsed_ms:.0f} ms"
        )
        if not findings:
            return
        report = "\n".join(f.format() for f in findings)
        if mode == "error":
            raise LintError(
                f"IR verifier found {len(findings)} problem(s) in the precompiled "
                f"step programs (doc/lint.md DML6xx; suppress with "
                f"'# dmllint: disable=ID'):\n{report}",
                findings=findings,
            )
        self.logger.warning("IR verifier findings in precompiled step programs:\n%s", report)

    def _pre_epoch(self):
        self._stall.reset()  # misc/host_stall_ms is a per-epoch total
        self._gp_data_wait_ns = 0
        self._gp_pad_slots = 0
        self._gp_token_slots = 0
        from .data import store as _shard_store

        self._gp_reader_mark = _shard_store.reader_activity()
        super()._pre_epoch()

    @property
    def _telemetry_armed(self) -> bool:
        return bool(getattr(self.pipeline, "telemetry_armed", False))

    def _reduce_metrics(self):
        # everything the host spent blocked this epoch (value fetches, the
        # epoch-end block_until_ready, waits on async checkpoint commits)
        self.track("misc/host_stall_ms", round(self._stall.ms, 3), prefixed=False)
        if self._telemetry_armed and self.epoch_stop_time is not None:
            # the goodput ledger's per-epoch buckets (telemetry/goodput.py):
            # disjoint by construction — data_wait is timed OUTSIDE the stall
            # timer, ckpt is the stall timer's 'checkpoint' share, and
            # productive is the remainder. MEAN-reduced across hosts on the
            # packed epoch-end collective like any other scalar metric.
            epoch_s = (self.epoch_stop_time - self.epoch_start_time).total_seconds()
            data_wait_ms = self._gp_data_wait_ns / 1e6
            ckpt_ms = self._stall.label_ms("checkpoint")
            stall_ms = self._stall.ms  # includes the checkpoint share
            productive_s = max(epoch_s - (data_wait_ms + stall_ms) / 1e3, 0.0)
            self.track_reduce(
                "misc/data_wait_ms", round(data_wait_ms, 3), reduction=Reduction.MEAN, prefixed=False
            )
            self.track_reduce(
                "misc/ckpt_ms", round(ckpt_ms, 3), reduction=Reduction.MEAN, prefixed=False
            )
            self.track_reduce(
                "misc/goodput",
                round(productive_s / epoch_s, 6) if epoch_s > 0 else 0.0,
                reduction=Reduction.MEAN,
                prefixed=False,
            )
            if self._gp_token_slots:
                self.track_reduce(
                    "misc/pad_fraction",
                    round(self._gp_pad_slots / self._gp_token_slots, 6),
                    reduction=Reduction.MEAN,
                    prefixed=False,
                )
            from .data import store as _shard_store

            if _shard_store.reader_activity() > getattr(self, "_gp_reader_mark", 0):
                # a ShardReader fetched blocks this epoch — the goodput
                # advisor points at reader knobs instead of generic prefetch
                self.track_reduce(
                    "misc/shard_reader", 1.0, reduction=Reduction.MAX, prefixed=False
                )
        if self._train_compiled is not None:
            # signatures that showed up this epoch WITHOUT a precompiled
            # executable — each one was a mid-run XLA compile (0 is the goal;
            # the TraceGuard wrapper has already warned per growth event)
            self.tracker.bump(
                "misc/recompiles",
                self._train_compiled.pop_recompiles() + self._val_compiled.pop_recompiles(),
            )
        super()._reduce_metrics()

    def _post_epoch(self):
        super()._post_epoch()
        self._maybe_save_state()

    def _post_stage(self):
        # sync point: every async save this stage dispatched must be
        # committed before the stage is considered finished — a following
        # stage's restore, the run-end teardown, and a preemption exit
        # (mid-epoch or epoch-boundary, both route through here) all rely
        # on the newest checkpoint being durable at this line
        if self.pipeline.checkpoint_dir is not None:
            self.pipeline.checkpoint_dir.wait_until_finished(scope=self.name)
            self.pipeline.checkpoint_dir.wait_until_finished(scope=self._steps_scope)
        # publish trained params back to the registry so a following stage
        # continues from them (the reference's in-place nn.Module semantics)
        if self.state is not None:
            entry = self.pipeline._model_entry(self.model_name())
            entry.params = self.state.params
            entry.extras = self.state.extras
            # the averaged weights are what the val metrics (and any
            # best-checkpoint ranking) were computed on — hand them onward too
            entry.ema = self.state.ema
        super()._post_stage()

    # -- automatic state checkpointing (closes reference gap, SURVEY.md §3.5) --
    def _state_pytree(self) -> dict:
        tree = {
            "step": self.state.step,
            "params": self.state.params,
            "opt_state": self.state.opt_state,
            "rng": self.state.rng,
        }
        if self.state.extras is not None:
            tree["extras"] = self.state.extras
        if self.state.ema is not None:
            tree["ema"] = self.state.ema
        return tree

    def _maybe_save_state(self):
        ckpt = self.pipeline.checkpoint_dir
        every = int(self.checkpoint_every())
        if ckpt is None or every <= 0 or self.state is None:
            return
        completed = self.current_epoch - 1  # super()._post_epoch incremented
        final = completed == self.max_epochs or self._stop_requested or self._preempt_exit
        if completed % every != 0 and not final:
            return
        save_kwargs = {}
        best_metric = self.checkpoint_best_metric()
        if best_metric is not None:
            hist = self.tracker[best_metric] if best_metric in self.tracker else []
            val = hist[-1] if hist else None
            if val is None:
                self.logger.warning(
                    f"checkpoint_best_metric {best_metric!r} has no value for epoch "
                    f"{completed}; this save is unranked (retained only while it is the newest)"
                )
            else:
                save_kwargs["metrics"] = {best_metric: float(val)}
        # single-flight: an async save still committing from a previous epoch
        # is waited out (timed as stall) before the new one dispatches. The
        # save call itself is timed too — async it costs one D2H snapshot,
        # sync (async_checkpoint() False) it blocks for the full commit.
        t0 = time.perf_counter()
        with self._stall.measure(label="checkpoint"):
            ckpt.wait_until_finished(scope=self.name)
            ckpt.save_state(completed, self._state_pytree(), scope=self.name, **save_kwargs)
        self._last_save_latency_s = time.perf_counter() - t0
        if is_root():
            from .utils.serialization import to_jsonable

            try:
                tracker_state = to_jsonable(self.tracker.state_dict())
            except TypeError as e:
                # a non-numeric tracked value must not kill the run at save
                # time (worse: only root would die, the other hosts would hang
                # in the next collective) — save epoch/stop without history
                self.logger.warning(
                    f"Metric tracker state is not JSON-encodable ({e}); saving resume "
                    "metadata without metric history"
                )
                tracker_state = None
            self._write_resume_sidecar(
                self.name,
                completed,
                {"epoch": completed, "stopped": self._stop_requested, "tracker": tracker_state},
            )

    def _write_resume_sidecar(self, scope: str, key: int, payload: dict) -> None:
        """Root-side sidecar write + retention cleanup, shared by the epoch
        and step save paths.

        Atomic write: a preemption mid-write must not leave a truncated
        sidecar that breaks the very resume it exists for. Cleanup keeps
        sidecars in lockstep with Orbax's COMMITTED saves (``all_steps``):
        with async saves the previous checkpoint stays the latest committed
        one until the new save lands, so its sidecar must survive until
        then — deleting by 'newest only' would strand the only restorable
        save without resume metadata after a crash mid-commit. ``*.pkl``
        covers sidecars from the pre-JSON format."""
        import json

        from .checkpoint import atomic_write_text

        ckpt = self.pipeline.checkpoint_dir
        meta_dir = ckpt.path / "meta" / scope
        meta_dir.mkdir(parents=True, exist_ok=True)
        atomic_write_text(meta_dir / f"{key}.json", json.dumps(payload))
        kept = set(ckpt.state_manager(scope).all_steps()) | {key}
        for f in list(meta_dir.glob("*.json")) + list(meta_dir.glob("*.pkl")):
            if f.stem.isdigit() and int(f.stem) not in kept:
                f.unlink(missing_ok=True)

    def _save_step_state(self, epoch_step: int) -> None:
        """Collective mid-epoch save keyed by the GLOBAL optimizer step, with
        a root-written sidecar recording where inside which epoch it landed
        (what a resume needs to fast-forward the data), under which world
        size (so a resume on a DIFFERENT process count re-derives its
        per-rank position), and — when the train dataset is resumable — its
        iterator state."""
        ckpt = self.pipeline.checkpoint_dir
        t0 = time.perf_counter()
        with self._stall.measure(label="checkpoint"):
            # at most one save in flight; the step-counter fetch blocks on
            # the dispatched steps, so both waits count as host stall — as
            # does the save call itself (one D2H snapshot when async, the
            # full blocking commit when async_checkpoint() is off)
            ckpt.wait_until_finished(scope=self._steps_scope)
            gstep = int(jax.device_get(self.state.step))
            ckpt.save_state(gstep, self._state_pytree(), scope=self._steps_scope)
        #: the preemption verdict's save-on-preempt latency (doc/elasticity.md)
        self._last_save_latency_s = time.perf_counter() - t0
        if is_root():
            payload = {
                "epoch": self.current_epoch,
                "step_in_epoch": epoch_step,
                "world_size": runtime.world_size(),
            }
            ds = self.pipeline.datasets.get("train")
            if hasattr(ds, "state_dict"):
                try:
                    payload["data"] = ds.state_dict()
                except Exception:
                    self.logger.warning(
                        "train dataset state_dict() failed; resume will fast-forward "
                        "by batch count instead", exc_info=True,
                    )
            self._write_resume_sidecar(self._steps_scope, gstep, payload)

    def _read_step_resume_meta(self, gstep: int) -> dict | None:
        """Root-only: the step-save sidecar, or None (degrade to epoch resume)."""
        import json

        meta_file = self.pipeline.checkpoint_dir.path / "meta" / self._steps_scope / f"{gstep}.json"
        try:
            raw = json.loads(meta_file.read_text())
            meta = {"epoch": int(raw["epoch"]), "step_in_epoch": int(raw["step_in_epoch"])}
            # optional elastic fields (absent in pre-elastic sidecars)
            meta["world_size"] = int(raw.get("world_size", runtime.world_size()))
            if isinstance(raw.get("data"), dict):
                meta["data"] = raw["data"]
            return meta
        except Exception:
            self.logger.warning(
                f"No usable step-resume metadata at {meta_file}; falling back (last "
                "completed epoch if one exists, else weights-only step restore)"
            )
            return None

    def _read_resume_meta(self, step: int) -> dict | None:
        """Root-only: read + validate the JSON resume sidecar for ``step``.
        Returns None (with a logged warning) on a missing/corrupt/ill-typed
        file — the caller degrades to Orbax-only resume."""
        import json

        from .utils.serialization import from_jsonable

        meta_file = self.pipeline.checkpoint_dir.path / "meta" / self.name / f"{step}.json"
        try:
            raw = json.loads(meta_file.read_text())
            meta = {
                "epoch": int(raw["epoch"]),
                "stopped": bool(raw["stopped"]),
                "tracker": from_jsonable(raw["tracker"]),
            }
            if meta["tracker"] is not None:
                # full validation: load into a throwaway tracker so a
                # structurally incomplete sidecar degrades here (to
                # Orbax-only resume) instead of crashing the real restore
                MetricTracker().load_state_dict(meta["tracker"])
            return meta
        except FileNotFoundError:
            legacy = meta_file.with_suffix(".pkl")
            if legacy.exists():
                self.logger.warning(
                    f"Found legacy pickle resume sidecar {legacy}; it is ignored (pickle "
                    "loading executes arbitrary code). Metric history and early-stop flag "
                    "start fresh; training state itself is fully restored from Orbax."
                )
            else:
                self.logger.warning(
                    f"No resume metadata at {meta_file}; continuing from the Orbax step alone "
                    "(metric history and early-stop flag are lost)"
                )
        except Exception:
            self.logger.warning(
                f"Corrupt resume metadata {meta_file}; continuing from the Orbax step alone "
                "(metric history and early-stop flag are lost)"
            )
        return None

    def _restore_tree(self, scope: str, key: int) -> dict:
        """Restore the state pytree from ``scope``/``key``, tolerating the
        one legitimate structure drift: ``ema_decay()`` toggled since the
        checkpoint was written. Any other mismatch re-raises."""
        ckpt = self.pipeline.checkpoint_dir
        template = self._state_pytree()
        try:
            return ckpt.restore_state(key, template=template, scope=scope)
        except Exception as err:
            alt = {k: v for k, v in template.items() if k != "ema"}
            if "ema" not in template:
                # abstract template leaves: no device allocation for a tree
                # that exists only to satisfy the structure match (its
                # restored arrays are dropped below)
                alt["ema"] = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape,
                        jnp.float32 if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x.dtype,
                    ),
                    template["params"],
                )
            try:
                restored = ckpt.restore_state(key, template=alt, scope=scope)
            except Exception:
                raise err from None
            if "ema" in template:
                self.logger.warning(
                    f"Checkpoint {key} for scope '{scope}' has no EMA tree "
                    "(ema_decay() was enabled after it was written); the shadow restarts "
                    "from the restored params"
                )
            else:
                self.logger.warning(
                    f"Checkpoint {key} for scope '{scope}' carries an EMA tree but "
                    "ema_decay() is now 0; the shadow is dropped"
                )
                restored.pop("ema", None)
            return restored

    def _restore_state(self):
        ckpt = self.pipeline.checkpoint_dir
        if ckpt is None or self.state is None:
            return
        # manual epoch checkpointing (checkpoint_every()==0) owns its scope's
        # keys — they need not be epoch numbers, so only step saves are
        # considered for automatic resume in that mode
        latest = ckpt.latest_step(scope=self.name) if int(self.checkpoint_every()) > 0 else None
        # a step-granular save mid-epoch may be fresher than the last
        # completed epoch (its sidecar records the epoch it was inside)
        step_meta = step_latest = None
        if int(self.checkpoint_every_steps()) > 0:
            step_latest = ckpt.latest_step(scope=self._steps_scope)
            if step_latest is not None:
                sm = self._read_step_resume_meta(step_latest) if is_root() else None
                sm = runtime.broadcast_object(sm)
                if sm is not None and sm["epoch"] > (latest or 0):
                    step_meta = sm
        # no epoch save to fall back on but a step save exists (step-only
        # mode, or a crash before the first epoch completed) with unusable
        # position metadata: restore the WEIGHTS rather than silently
        # training from scratch into the same checkpoint dir
        blind_step = latest is None and step_meta is None and step_latest is not None
        if latest is None and step_meta is None and not blind_step:
            return  # e.g. crash before this stage's first save
        if step_meta is not None or blind_step:
            restored = self._restore_tree(self._steps_scope, step_latest)
        else:
            restored = self._restore_tree(self.name, latest)
        self.state = self.state.replace(**restored)
        if self.state.ema is not None and "ema" not in restored:
            # EMA newly enabled on a resumed run: average from the restored
            # params, not the random init the fresh state copied
            from .train_state import ema_like

            self.state = self.state.replace(ema=ema_like(self.state.params))
        # The root alone reads and validates the sidecar, then broadcasts the
        # resolved (epoch, stopped, tracker) — if every process read its own
        # copy, a corrupt/missing file on SOME hosts would leave them with
        # different epoch counters and stop flags, so some hosts enter the
        # epoch loop's collectives while others skip it: divergence, then
        # deadlock. Same root-decides pattern as enable_checkpointing.
        if latest is not None:
            meta = self._read_resume_meta(latest) if is_root() else None
            meta = runtime.broadcast_object(meta)
        else:
            meta = None
        if meta is not None:
            if meta["tracker"] is not None:
                self.tracker.load_state_dict(meta["tracker"])
            self.current_epoch = meta["epoch"] + 1
            # a stage that had already stopped early must not re-train
            self._stop_requested = meta["stopped"]
        elif latest is not None:
            self.current_epoch = latest + 1
        if step_meta is not None:
            self.current_epoch = step_meta["epoch"]
            # elastic world-size scaling: the sidecar's batch count is
            # per-rank UNDER THE SAVED world size; re-derive this run's
            # per-rank skip from the world-size-independent global count
            saved_ws = int(step_meta.get("world_size", runtime.world_size()))
            ws = runtime.world_size()
            global_batches = step_meta["step_in_epoch"] * saved_ws
            skip, rem = divmod(global_batches, ws)
            if rem:
                self.logger.warning(
                    f"mid-epoch resume: {global_batches} globally-consumed batches do "
                    f"not divide the new world size {ws}; rounding down (up to "
                    f"{ws - 1} global batch(es) replay)"
                )
            self._resume_skip_steps = skip
            self._resume_data_state = step_meta.get("data")
            # sparse checkpoint_every (>1): the restored tracker may trail
            # the resumed epoch — pad the gap (None entries) so every later
            # epoch's metrics stay aligned with its epoch number
            self.tracker.fast_forward(self.current_epoch)
            self.logger.info(
                f"Restored stage '{self.name}' from mid-epoch step save (global step "
                f"{step_latest}); continuing epoch {self.current_epoch} at batch "
                f"{self._resume_skip_steps}"
                + (f" (resharded from world size {saved_ws})" if saved_ws != ws else "")
            )
        elif blind_step:
            self.logger.warning(
                f"Restored stage '{self.name}' WEIGHTS from step save {step_latest} but its "
                "position metadata was unusable: the epoch loop restarts at epoch "
                f"{self.current_epoch} on the restored state"
            )
        else:
            self.logger.info(
                f"Restored stage '{self.name}' state from epoch {latest}; continuing at epoch {self.current_epoch}"
            )

    def _cost_analysis_flops(self) -> float:
        """MFU fallback when ``step_flops()`` is not declared: whole-mesh
        FLOPs of one step from the AOT-compiled executable's own XLA cost
        analysis (0.0 when no compiled executable or no counter — the MFU
        metric is then skipped, never invented). Cached: the analysis is
        signature-independent to first order."""
        if self._cost_flops is None:
            val = 0.0
            if self._train_compiled is not None:
                exe = self._train_compiled.any_compiled()
                if exe is not None:
                    from .telemetry.goodput import flops_from_compiled

                    val = flops_from_compiled(exe, n_devices=int(self.mesh.devices.size)) or 0.0
            self._cost_flops = val
        return self._cost_flops

    def run_epoch(self):
        self.train_epoch()
        if self._mid_epoch_exit:
            return  # preempted at a step boundary: no val on a partial epoch
        self.val_epoch()

    def _put(self, batch):
        """Move a host batch onto the mesh with batch sharding; pass through
        anything already device-resident."""
        return mesh_lib.make_global_batch(batch, self.mesh)

    def _feed(self, ds):
        """The device feeding path: mesh-sharded batches with
        ``prefetch_depth()`` transfers in flight ahead of the step — and
        optionally ``host_prefetch()`` host batches prepared on a background
        thread (data/device.py) — or per-step synchronous puts when disabled.
        With ``buckets()`` armed, batches are bucket-padded (+ mask) on host
        BEFORE the transfer, so the device only ever sees bucket shapes."""
        if self._telemetry_armed:
            ds = self._count_padding(ds)
        if self._buckets_resolved:
            from .compile.buckets import bucket_iterator

            ds = bucket_iterator(ds, self._buckets_resolved, mask_key=self.bucket_mask_key())
        prefetch = int(self.prefetch_depth())
        if prefetch > 0:
            from .data.device import device_iterator

            return device_iterator(
                ds, self.mesh, prefetch=prefetch, host_prefetch=int(self.host_prefetch())
            )
        return (self._put(batch) for batch in ds)

    def _count_padding(self, ds):
        """Account padding in HOST batches that carry ``segment_ids`` (the
        packed/pad-masked input contract, doc/data.md): slots with id 0 are
        padding — FLOPs the step burns without learning. Feeds
        ``misc/pad_fraction`` and the goodput advisor's "enable
        pack_stream" suggestion. Telemetry-armed runs only (one numpy
        compare per batch, before any device transfer); non-numpy leaves
        (already-on-device batches) are left untouched — no implicit D2H."""
        for batch in ds:
            if isinstance(batch, dict):
                seg = batch.get("segment_ids")
                if isinstance(seg, np.ndarray) and seg.size:
                    self._gp_pad_slots += int(np.count_nonzero(seg == 0))
                    self._gp_token_slots += int(seg.size)
            yield batch

    def _timed_feed(self, ds):
        """``_feed`` with each ``next()`` timed as the goodput ledger's
        data_wait bucket (+ a journal span per batch). Only interposed when
        telemetry is armed — the default feeding path is untouched."""
        it = iter(self._feed(ds))
        try:
            while True:
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    return
                t1 = time.perf_counter()
                self._gp_data_wait_ns += int((t1 - t0) * 1e9)
                _journal.emit("data_wait", t0, t1)
                yield batch
        finally:
            # abandonment (preemption drain) must reach the device iterator's
            # own shutdown path promptly, not wait for GC
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _feed_for_epoch(self, ds):
        return self._timed_feed(ds) if self._telemetry_armed else self._feed(ds)

    def train_epoch(self):
        self.is_train = True
        self.metric_prefix = self.train_metric_prefix()

        train_ds = self.train_dataset()
        if hasattr(train_ds, "set_epoch"):
            train_ds.set_epoch(self.current_epoch)
        elif hasattr(train_ds, "sampler") and hasattr(getattr(train_ds, "sampler"), "set_epoch"):
            train_ds.sampler.set_epoch(self.current_epoch)

        # mid-epoch resume: fast-forward the deterministic per-epoch
        # iteration past the batches the interrupted run already consumed
        # (host-side skip — no device transfers for skipped batches). A
        # resumable dataset (DataPipeline.load_state_dict) fast-forwards
        # itself from the saved iterator state — same elements, but the
        # cursor survives world-size changes and future step saves keep
        # checkpointing coherent offsets.
        skipped = self._resume_skip_steps
        self._resume_skip_steps = 0
        data_state = self._resume_data_state
        self._resume_data_state = None
        if data_state is not None and hasattr(train_ds, "load_state_dict"):
            train_ds.load_state_dict(data_state)
            self.logger.info(
                f"mid-epoch resume: train dataset fast-forwarded from saved iterator "
                f"state {data_state} for epoch {self.current_epoch}"
            )
        elif skipped:
            import itertools

            train_ds = itertools.islice(iter(train_ds), skipped, None)
            self.logger.info(
                f"mid-epoch resume: skipping the first {skipped} batches of epoch {self.current_epoch}"
            )
        every_steps = int(self.checkpoint_every_steps())
        if self.pipeline.checkpoint_dir is None:
            every_steps = 0

        # Deferred-readback plumbing (overlap engine). Deferred (default):
        # losses ride a short rolling window of device arrays whose D2H
        # copies are issued non-blocking at dispatch time; the host touches
        # a value only every log_every() steps — a 2-3-step-TRAILING fetch
        # that is already computed AND already copied, so the sync point
        # costs ~nothing. The NaN/inf guard and the live console EMA both
        # piggyback on that one periodic fetch. Eager (deferred_metrics()
        # False, the bisection baseline): every step's metrics are pulled to
        # host immediately, timed as stall.
        live = self.table.live_target() is not None
        deferred = bool(self.deferred_metrics())
        log_every = int(self.log_every())
        guard = bool(self.nan_guard())
        loss_name = self.loss_metric_name()
        pending_losses: list = []
        loss_ema = None
        steps_done = 0
        epoch_t0 = time.perf_counter()
        last_render = 0.0

        def _guard_loss(v: float, at_step: int) -> None:
            if guard and not np.isfinite(v):
                raise FloatingPointError(
                    f"non-finite loss ({v}) detected at step {at_step} of epoch "
                    f"{self.current_epoch} (stage {self.name!r})"
                )

        last_metrics = None
        self._in_step_loop = True
        feed = self._feed_for_epoch(train_ds)
        try:
            for batch in feed:
                step_start = time.perf_counter_ns()
                self.state, metrics = self._train_step_fn(self.state, batch)
                step_end = time.perf_counter_ns()
                j = _journal.active_journal()
                if j is not None:  # off: two clock readings and nothing built
                    j.emit("step_dispatch", step_start / 1e9, step_end / 1e9, step=steps_done + 1)

                if not deferred:
                    with self._stall.measure(label="metric_readback"):  # eager per-step readback
                        metrics = jax.device_get(metrics)
                for mname, mval in metrics.items():
                    self.track_reduce(mname, mval)
                self.track_reduce("misc/total_train_batches", 1, reduction=Reduction.SUM, prefixed=False)
                self.track_reduce(
                    "misc/worker_train_batches", 1, reduction=Reduction.SUM, reduce_globally=False, prefixed=False
                )
                # dispatch-to-dispatch time: how long the host took to enqueue the
                # step. Under async dispatch this is NOT device execution time —
                # see misc/train_step_avg_ms for the wall-clock per-step average.
                self.track_reduce("misc/step_dispatch_ms", (step_end - step_start) / 1e6, prefixed=False)
                last_metrics = metrics

                steps_done += 1
                if every_steps and (skipped + steps_done) % every_steps == 0:
                    self._save_step_state(skipped + steps_done)
                    if self.pipeline._preemption_coordinated():
                        # the save just above is the resume point; cut the epoch
                        # here instead of finishing it (Stage.run handles exit)
                        self._mid_epoch_exit = True
                        break

                loss_val = metrics.get(loss_name)
                if deferred:
                    if loss_val is not None and (live or (guard and log_every > 0)):
                        copy_async = getattr(loss_val, "copy_to_host_async", None)
                        if copy_async is not None:
                            try:
                                copy_async()
                            except Exception:
                                pass
                        pending_losses.append(loss_val)
                        if len(pending_losses) > 3:
                            pending_losses.pop(0)
                    if log_every > 0 and steps_done % log_every == 0 and pending_losses:
                        v = float(self._stall.fetch(pending_losses[0]))
                        loss_ema = v if loss_ema is None else 0.98 * loss_ema + 0.02 * v
                        _guard_loss(v, steps_done)
                elif loss_val is not None:
                    # eager bisection path: the value is already host-side
                    # (fetched under the stall timer in the device_get above)
                    # dmllint: disable-next-line=DML101 -- converts, not syncs
                    v = float(np.asarray(loss_val))
                    loss_ema = v if loss_ema is None else 0.98 * loss_ema + 0.02 * v
                    _guard_loss(v, steps_done)

                if live:
                    now = time.perf_counter()
                    if now - last_render > 0.25:
                        self.table.live(
                            {
                                "Epoch": self.current_epoch,
                                "[Train] Loss": loss_ema,
                                "it/s": steps_done / max(now - epoch_t0, 1e-9),
                            }
                        )
                        last_render = now
        finally:
            self._in_step_loop = False
            # deterministic feed shutdown: a break (mid-epoch preemption
            # drain) must stop the prefetch machinery NOW — its background
            # thread joins within one put timeout — not at GC time
            close = getattr(feed, "close", None)
            if close is not None:
                close()

        # Close the async pipeline BEFORE the epoch wall-clock reading so the
        # per-step average below reflects device execution, then derive the
        # honest number users actually want from "step time". This is THE
        # epoch sync point: past this line every dispatched step has
        # executed and host-side state (tracker buffers, self.state) is
        # guaranteed current.
        if last_metrics is not None:
            self._stall.block(last_metrics)
        if self._mid_epoch_exit:
            # partial epoch: skip epoch-level metrics — the resumed run
            # finishes the epoch and reduces over its remaining steps
            return
        train_elapsed = time.perf_counter() - epoch_t0
        if steps_done:
            self.track("misc/train_step_avg_ms", train_elapsed / steps_done * 1e3, prefixed=False)
            flops = float(self.step_flops())
            if flops <= 0 and self._telemetry_armed:
                flops = self._cost_analysis_flops()
            if flops > 0:
                from .utils.profiling import peak_flops_for_kind

                kind = jax.local_devices()[0].device_kind
                peak = peak_flops_for_kind(kind)
                if peak is None:
                    # no honest denominator for this backend (CPU/GPU dev
                    # runs): skip the metric rather than log a fiction
                    if not getattr(self, "_warned_mfu_peak", False):
                        self._warned_mfu_peak = True
                        self.logger.warning(
                            f"device kind {kind!r} is not in the bf16 peak table; "
                            "misc/mfu will not be tracked on this backend"
                        )
                else:
                    peak_total = peak * int(self.mesh.devices.size)
                    self.track(
                        "misc/mfu", flops * steps_done / train_elapsed / peak_total, prefixed=False
                    )
        self.table["it/s"] = steps_done / max(train_elapsed, 1e-9)

        for name, schedule in self.pipeline.schedulers.items():
            if self.state is not None:
                with self._stall.measure(label="metric_readback"):
                    step_count = int(jax.device_get(self.state.step))
            else:
                step_count = 0
            self.track(f"misc/lr_{name}", float(schedule(step_count)), prefixed=False)

    def val_epoch(self):
        self.is_train = False
        self.metric_prefix = self.val_metric_prefix()

        try:
            val_ds = self.val_dataset()
        except DatasetNotFoundError:
            # val dataset optional in the TPU build. ONLY the sentinel is
            # swallowed — an arbitrary ValueError raised by a user
            # val_dataset() override is a bug and must surface, not silently
            # skip validation forever.
            return

        deferred = bool(self.deferred_metrics())
        last_metrics = None
        for batch in self._feed_for_epoch(val_ds):
            metrics = self._val_step_fn(self.state, batch)
            if not deferred:
                with self._stall.measure(label="metric_readback"):  # eager per-step readback
                    metrics = jax.device_get(metrics)
            for mname, mval in metrics.items():
                self.track_reduce(mname, mval)
            self.track_reduce("misc/total_val_batches", 1, reduction=Reduction.SUM, prefixed=False)
            self.track_reduce(
                "misc/worker_val_batches", 1, reduction=Reduction.SUM, reduce_globally=False, prefixed=False
            )
            last_metrics = metrics
        if last_metrics is not None:
            self._stall.block(last_metrics)

    def table_columns(self):
        columns = super().table_columns()
        columns.insert(1, {"name": "[Train] Loss", "metric": f"{self.train_metric_prefix()}/{self.loss_metric_name()}"})
        columns.insert(2, {"name": "[Val] Loss", "metric": f"{self.val_metric_prefix()}/{self.loss_metric_name()}"})
        columns.insert(3, {"name": "it/s", "metric": None})  # live + epoch average
        return columns
