"""Paged KV-cache indexing: gather/scatter between a block pool and
per-sequence block tables.

The serving engine (``dmlcloud_tpu/serve/``) keeps the KV cache as a fixed
pool of ``[num_blocks, block_size, KH, D]`` pages per layer instead of one
dense ``[B, max_len, KH, D]`` buffer per request batch: each sequence owns
a short list of pool blocks (its *block table*), so cache memory scales
with the tokens actually live and a finished sequence's blocks recycle to
the next request immediately. These two functions are the traced index
arithmetic that makes the pool usable from inside a jitted decode step:

- :func:`scatter_tokens` writes a batch of new K/V rows into the pages the
  block tables name (one vectorized scatter — the paged twin of the dense
  path's ``dynamic_update_slice``);
- :func:`gather_pages` reassembles each sequence's pages into a contiguous
  ``[B, NB*block_size, KH, D]`` view for attention, which then runs through
  the SAME masked GQA attention as the dense decode path
  (``models/transformer._dot_attention`` with the causal/window predicate
  ``_window_keep`` — the Mistral-convention machinery the flash kernels in
  ``ops/flash_attention.py`` block-tile).

Both functions are multi-token per row by construction — ``T`` is just a
shape axis. Chunked prefill writes ``prefill_chunk`` positions per call,
and the speculative engine's rounds lean on the same property: a draft
pass writes 2 then 1 positions, the verification pass scatters all
``k+1`` proposal positions per sequence through the block tables in ONE
call (and gathers once for the whole round) — the multi-token round cost
that replaces plain decode's per-token cost (serve/engine.py).

Prefix sharing (``serve/prefix_cache.py``) adds one asymmetric contract:
the SAME physical block may appear in many rows' tables (and in many
concurrent batches) — :func:`gather_pages` needs nothing special for
that, every row just reads the shared page. :func:`scatter_tokens` is the
dangerous half: a write through a table entry whose block has
``refcount > 1`` would corrupt every other reader's prefix, so the
serving engine copy-on-write forks (or refcount-checks) BEFORE building
the tables it scatters through — refcounts are host state, invisible to
this traced code, which is exactly why the ordering is enforced
statically by lint rule DML211 rather than here.

Out-of-range handling is the whole trick for static shapes: block tables
are padded with a SENTINEL entry equal to ``num_blocks`` (one past the
pool). jax clips out-of-bounds *gather* indices — the sentinel reads the
last real block, and the caller's ``kv_pos <= q_pos`` mask hides whatever
it read — and ``mode="drop"`` discards out-of-bounds *scatter* updates, so
a padded batch row (or a prefill chunk's padded tail spilling past its
allocation) writes nothing at all. A NEGATIVE position maps below the
table and is redirected to the sentinel the same way — it can never wrap
into a real block (tests/test_serve.py locks both). Inactive rows
therefore cost index arithmetic only; no branch, no dynamic shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gather_pages", "scatter_tokens"]


@jax.named_scope("kv_gather")
def gather_pages(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Reassemble each row's pages into a contiguous KV view.

    ``pool`` is ``[num_blocks, block_size, KH, D]``; ``tables`` is
    ``[B, NB]`` int32 physical block ids (sentinel ``num_blocks`` for
    unused entries — clipped by the gather, masked by the caller).
    Returns ``[B, NB * block_size, KH, D]``: row ``b``'s token position
    ``p`` lives at gathered index ``p`` for every ``p < fill[b]``, exactly
    the dense cache layout attention already understands.
    """
    g = pool[tables]  # [B, NB, bs, KH, D]; OOB table entries clip
    return g.reshape(tables.shape[0], tables.shape[1] * pool.shape[1], *pool.shape[2:])


@jax.named_scope("kv_write")
def scatter_tokens(
    pool: jnp.ndarray, tables: jnp.ndarray, positions: jnp.ndarray, values: jnp.ndarray
) -> jnp.ndarray:
    """Write per-token K/V rows into the pages their block tables name.

    ``positions`` is ``[B, T]`` absolute token positions (position ``p``
    lands in logical block ``p // block_size``, slot ``p % block_size``);
    ``values`` is ``[B, T, KH, D]``. A position whose logical block falls
    outside its table row — a padded batch row carrying a sentinel-only
    table, a prefill pad tail past the row's allocation, or a negative
    position — maps to the out-of-bounds sentinel and is DROPPED by the
    scatter, not written. Returns the updated pool.
    """
    num_blocks, block_size = pool.shape[0], pool.shape[1]
    nb = tables.shape[1]
    block = positions // block_size  # [B, T] logical block index
    slot = positions % block_size
    phys = jnp.take_along_axis(tables, jnp.clip(block, 0, nb - 1), axis=1)
    # a logical block past the table's width must not clip INTO the row's
    # last real block — redirect it to the drop sentinel explicitly
    phys = jnp.where((block >= 0) & (block < nb), phys, num_blocks)
    return pool.at[phys, slot].set(values.astype(pool.dtype), mode="drop")
