"""Test fixtures: a virtual 8-device CPU mesh in one process.

The reference fakes a cluster with a world-size-1 HashStore process group
(/root/reference/test/conftest.py:6-10). The TPU build goes further: XLA's
host-platform device count gives *real* multi-device pjit/psum execution on
CPU (SURVEY.md §4 testing blueprint) — sharding bugs show up for real.

Must run before any test imports trigger backend initialisation.
"""

import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
# The suite is compile-bound (about a thousand tiny programs) and what it
# checks is this package, not how hard LLVM optimises XLA:CPU code: level 0
# took the tier-1 run from 936 s to 706 s on the 8-core sandbox with the same
# outcome test for test (CHANGES.md PR 21). The driver's limit is 1,470 s on
# six workers.
os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

import jax

jax.config.update("jax_platforms", "cpu")
# Serial dispatch: concurrent collective programs starve XLA:CPU's rendezvous
# on few-core CI machines (see pipeline._init_mesh).
jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402

# NOTE: do NOT arm the persistent XLA compilation cache (compile/cache.py)
# globally here, tempting as it is for the engine-heavy serve tests: on
# this jax/XLA:CPU, cache-deserialized executables destabilize the live
# 8-device collective programs later in the suite (segfault in
# test_resume's pipeline run — same failure family as the known
# jax.clear_caches() hazard, see CHANGES.md PR 3).
# TrainingPipeline and ServeEngine turn the cache on by default, so the
# session says so with jax's own switch (configure_cache honours it); the
# environment variable carries the same to the worker processes tests start.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)

from dmlcloud_tpu.parallel import runtime  # noqa: E402
from dmlcloud_tpu.telemetry import journal as _journal  # noqa: E402

# The package's import is journalled by the first journal armed in the process
# (telemetry/journal.py), and which test arms one first depends on a worker's
# order of files: the session starts with it taken, so a test sees the spans
# it made (tests/test_setup_spans.py sets its own readings).
_journal._import_readings = None


@pytest.fixture
def single_runtime():
    """Single-process runtime (the reference's dummy process group analog)."""
    runtime.init_single()
    yield
    runtime.deinitialize()


@pytest.fixture
def mesh8():
    """An 8-device data-parallel mesh on the forced CPU devices."""
    from dmlcloud_tpu.parallel import mesh as mesh_lib

    assert len(jax.devices()) == 8, "conftest must run before backend init"
    return mesh_lib.create_mesh({"data": -1})


# ---------------------------------------------------------------------------
# Session-scoped model fixtures (ROADMAP item 5c: tier-1 wall-time budget).
#
# test_serve, test_serve_router, test_speculative and test_quant each used
# to init their own per-module copy of the same tiny LMs; building each
# exactly ONCE per session removes the redundant inits and the re-traced
# init programs from the suite's wall clock. All consumers treat params as
# immutable (engines copy into pools, LoRA builds new trees), so sharing
# one instance across files is safe.
# ---------------------------------------------------------------------------


def _init_lm(cfg_kw, seed, init_len=4):
    import jax.numpy as jnp

    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    cfg = TransformerConfig(dtype=jnp.float32, **cfg_kw)
    model = DecoderLM(cfg)
    params = model.init(
        jax.random.PRNGKey(seed), jnp.ones((1, init_len), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="session")
def tiny_model():
    """The 61-vocab fp32 serve model shared by test_serve/test_serve_router:
    exact arithmetic so token-identity assertions are bitwise-ish."""
    return _init_lm(
        dict(vocab_size=61, num_layers=2, num_heads=4, num_kv_heads=2,
             head_dim=8, hidden_dim=32, mlp_dim=64, max_seq_len=64),
        seed=0,
    )


@pytest.fixture(scope="session")
def spec_models():
    """Target (2-layer) + independent random draft (1-layer) pair for the
    speculative-decoding exactness suite (test_speculative)."""
    import jax.numpy as jnp
    import numpy as np

    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    def lm(layers, seed):
        cfg = TransformerConfig(
            vocab_size=48, num_layers=layers, num_heads=2, num_kv_heads=1,
            head_dim=8, hidden_dim=16, mlp_dim=32, max_seq_len=96,
            dtype=jnp.float32,
        )
        model = DecoderLM(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 48, (1, 8)), jnp.int32
        )
        return model, model.init(jax.random.PRNGKey(seed), tokens)["params"]

    target, tparams = lm(2, 0)
    draft, dparams = lm(1, 7)
    return target, tparams, draft, dparams


@pytest.fixture(scope="session")
def quant_lm():
    """64-vocab LM for the weight-only int8 decode tests (test_quant)."""
    import jax.numpy as jnp
    import numpy as np

    from dmlcloud_tpu.models.transformer import DecoderLM, TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=2, num_kv_heads=1, head_dim=8,
        hidden_dim=16, mlp_dim=32, max_seq_len=48, dtype=jnp.float32,
    )
    model = DecoderLM(cfg)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (1, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return model, params
