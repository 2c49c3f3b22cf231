"""dmlcloud_tpu — a TPU-native distributed-training framework.

Same capabilities as sehoffmann/dmlcloud (Pipeline/Stage lifecycle, one-call
cluster bootstrap, distributed metrics, checkpoint dirs with requeue-resume,
reproducibility diagnostics, W&B glue, dataset sharding), rebuilt idiomatically
on JAX/XLA: device meshes + NamedSharding instead of DDP, one compiled donated
step instead of hook-driven allreduce, the jax.distributed coordination
service instead of c10d rendezvous, and Orbax for sharded tensor state.
"""

from time import perf_counter as _perf_counter

#: the ``import`` set-up span's start; the first journal armed records it (telemetry/journal.py)
_T_IMPORT = _perf_counter()

from . import compile, data, lint, metrics, parallel, telemetry, utils  # noqa: E402
from .checkpoint import CheckpointDir, find_slurm_checkpoint, generate_checkpoint_path  # noqa: E402
from .metrics import MetricReducer, MetricTracker, Reduction  # noqa: E402
from .pipeline import TrainingPipeline  # noqa: E402
from .stage import DatasetNotFoundError, Stage, TrainValStage  # noqa: E402
from .train_state import TrainState  # noqa: E402

__version__ = "0.5.0"

telemetry.journal.record_import(_T_IMPORT, _perf_counter())

__all__ = [
    "compile",
    "data",
    "lint",
    "metrics",
    "parallel",
    "telemetry",
    "utils",
    "CheckpointDir",
    "find_slurm_checkpoint",
    "generate_checkpoint_path",
    "MetricReducer",
    "MetricTracker",
    "Reduction",
    "TrainingPipeline",
    "DatasetNotFoundError",
    "Stage",
    "TrainValStage",
    "TrainState",
    "__version__",
]
