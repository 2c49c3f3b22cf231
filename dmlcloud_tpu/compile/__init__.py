"""dmlcloud_tpu.compile — the cold-start killer.

Three parts, composable but independent (doc/performance.md §4):

- :mod:`.cache` — persistent XLA compilation cache wiring + stats: compile
  once per *cluster*, not once per process (on by default in
  ``TrainingPipeline`` and ``ServeEngine``; ``$JAX_COMPILATION_CACHE_DIR``
  or ``<checkout>/.jax_cache``).
- :mod:`.aot` — ahead-of-time compilation of the jitted train/val steps
  against abstract batch specs: compile cost lands in a timed ``precompile``
  phase before the data loop (``misc/compile_ms``), and sharding/shape
  mismatches error at stage start instead of step 1
  (``TrainingPipeline(precompile=True)`` / ``Stage.precompile()``).
- :mod:`.buckets` — shape bucketing for ragged batches: pad to a small fixed
  bucket set with a zero-weight ``sample_mask``, so the compiled-signature
  count is bounded by ``len(buckets)`` and ``misc/recompiles`` stays 0
  (``TrainingPipeline(buckets=(...,))`` / ``Stage.buckets()``).
"""

from .aot import (
    PrecompiledStep,
    abstract_spec,
    global_batch_spec,
    signature_of,
    validate_global_batch_spec,
)
from .buckets import (
    DEFAULT_MASK_KEY,
    bucket_for,
    bucket_iterator,
    bucket_spec,
    masked_mean,
    masked_sum,
    pad_to_bucket,
    resolve_buckets,
)
from .cache import cache_stats, configure_cache, configured_cache_dir, resolve_cache_dir

__all__ = [
    "PrecompiledStep",
    "abstract_spec",
    "global_batch_spec",
    "signature_of",
    "validate_global_batch_spec",
    "DEFAULT_MASK_KEY",
    "bucket_for",
    "bucket_iterator",
    "bucket_spec",
    "masked_mean",
    "masked_sum",
    "pad_to_bucket",
    "resolve_buckets",
    "cache_stats",
    "configure_cache",
    "configured_cache_dir",
    "resolve_cache_dir",
]
