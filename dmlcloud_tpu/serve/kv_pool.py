"""The paged KV-cache block pool: fixed device pages, host-side free list.

Dense decode (``models/generate.py``) allocates ``[B, prompt + max_new,
KH, D]`` per layer for every call — memory scales with the WORST CASE of
every slot, and a sequence that finishes early keeps its whole allocation
until the batch drains. The pool inverts that: one fixed set of
``[num_blocks, block_size, KH, D]`` pages per layer lives on device for
the engine's whole lifetime, each sequence owns just the blocks its live
tokens occupy (its *block table*), and a finished sequence's blocks go
back on the free list the moment it emits EOS — cache memory scales with
**live tokens**, not max-length × batch.

Memory math (why this wins): with ``n`` concurrent requests of mean live
length ``L`` and max length ``S``, the dense cache holds ``n*S`` token
slots while the pool holds ``~n*L`` rounded up to blocks — at the typical
``L << S`` (most requests are short; ``S`` must cover the longest) the
pool serves the same traffic in a fraction of the HBM, or serves
``S/L``-fold more concurrent streams in the same HBM.

The pool object is deliberately split-brained:

- ``pools`` is the DEVICE half — a pytree shaped like ``init_cache``'s
  (``{layer_i: {k, v}}``) whose leaves are the page arrays. It rides
  through the engine's jitted step as a donated argument
  (``ops/paged_attention.py`` does the traced gather/scatter), and the
  engine writes the step's output back via :meth:`swap`.
- The free list / live set is the HOST half. Allocation never touches the
  device: handing out a block is popping an int. Double-free and
  foreign-block frees raise immediately — the invariant ``free + live ==
  capacity`` is load-bearing for a server that must not leak a block per
  million requests (property-tested in tests/test_serve.py).

Blocks are REFERENCE-COUNTED (PR 11, prefix sharing): ``alloc`` hands a
block out with one reference, :meth:`retain` adds holders (a prefix-cache
hit maps the same physical block into another request's table, the radix
tree itself holds one reference per cached block), :meth:`release` drops
one — the block returns to the free list only when its LAST holder lets
go. ``live`` counts UNIQUE referenced blocks, so the invariant becomes
``free + sum(1 for each unique live block) == capacity`` — sharing never
changes the total. A block with ``refcount > 1`` is READ-ONLY: the paged
scatter must never write through it (the engine's copy-on-write guard
forks first; lint rule DML211 enforces the ordering statically).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

__all__ = ["KVBlockPool", "PoolExhausted"]


class PoolExhausted(RuntimeError):
    """An allocation asked for more blocks than the pool has free."""


class KVBlockPool:
    """Fixed pool of KV pages per layer + host-side block accounting."""

    def __init__(
        self,
        num_layers: int,
        kv_heads: int,
        head_dim: int,
        *,
        num_blocks: int,
        block_size: int,
        dtype: Any = jnp.bfloat16,
    ):
        if num_blocks < 1 or block_size < 1:
            raise ValueError(
                f"need num_blocks >= 1 and block_size >= 1, got {num_blocks}/{block_size}"
            )
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = dtype
        shape = (self.num_blocks, self.block_size, int(kv_heads), int(head_dim))
        #: device half: the page arrays, init_cache-shaped ({layer_i: {k, v}})
        self.pools = {
            f"layer_{i}": {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
            for i in range(int(num_layers))
        }
        # host half: low ids hand out first (pop from the end of a reversed
        # stack) — purely cosmetic determinism that makes tests readable
        self._free: list[int] = list(range(self.num_blocks - 1, -1, -1))
        self._ref: dict[int, int] = {}  # live block -> reference count

    @classmethod
    def for_model(cls, cfg, *, num_blocks: int, block_size: int, dtype: Any = None) -> "KVBlockPool":
        """Pool sized for a ``TransformerConfig`` (dtype defaults to the
        model's compute dtype, matching ``init_cache``)."""
        return cls(
            cfg.num_layers, cfg.kv_heads, cfg.head_dim,
            num_blocks=num_blocks, block_size=block_size,
            dtype=cfg.dtype if dtype is None else dtype,
        )

    # -- accounting ----------------------------------------------------------
    @property
    def sentinel(self) -> int:
        """The out-of-bounds table entry (``num_blocks``): gathers through
        it are masked, scatters through it are dropped."""
        return self.num_blocks

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        """UNIQUE referenced blocks — a block mapped into three tables (or
        pinned by the radix tree) still counts once, so ``free + live ==
        capacity`` holds under arbitrary sharing."""
        return len(self._ref)

    def refcount(self, block: int) -> int:
        """Current holders of ``block`` (0 = free / not from this pool)."""
        return self._ref.get(int(block), 0)

    def is_shared(self, block: int) -> bool:
        """More than one holder: the block is READ-ONLY — any write must
        copy-on-write fork first (the DML211 contract)."""
        return self._ref.get(int(block), 0) > 1

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` cache slots."""
        return -(-int(tokens) // self.block_size)

    def bytes_per_block(self) -> int:
        leaves = next(iter(self.pools.values()))
        per_layer = sum(int(x.dtype.itemsize) * self.block_size * x.shape[2] * x.shape[3]
                        for x in leaves.values())
        return per_layer * len(self.pools)

    def stats(self) -> dict:
        """The pool's accounting snapshot (``free + live == capacity`` by
        construction): the utilization observable the serving scorecard
        records — a draft-model speculative engine pays
        for TWO of these (target + draft pages), and this is the number
        that says what the draft pool actually costs (and what Medusa
        mode, which has no second pool, wins back)."""
        return {
            "capacity": self.num_blocks,
            "free": self.num_free,
            "live": self.num_live,
            "shared": sum(1 for c in self._ref.values() if c > 1),
            "block_size": self.block_size,
            "bytes_total": self.bytes_per_block() * self.num_blocks,
        }

    def assert_consistent(self) -> None:
        """Audit the host accounting itself: every id in exactly one of
        {free list, live set}, counts positive, ids in range, and
        ``free + unique-live == capacity``. Raises ``AssertionError``
        with the discrepancy — the chaos drill runs this after every
        injected fault so a corrupted free list can never hide behind a
        numerically-balanced invariant."""
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate ids on the free list"
        live = set(self._ref)
        assert not (free & live), f"blocks both free and live: {sorted(free & live)}"
        assert len(free) + len(live) == self.num_blocks, (
            f"free ({len(free)}) + live ({len(live)}) != capacity ({self.num_blocks})"
        )
        bad = [b for b in self._ref if not 0 <= b < self.num_blocks]
        assert not bad, f"live ids out of range: {bad}"
        neg = [b for b, c in self._ref.items() if c < 1]
        assert not neg, f"non-positive refcounts: {neg}"

    # -- alloc / retain / release --------------------------------------------
    def alloc(self, n: int) -> list[int]:
        """Hand out ``n`` free blocks, each with ONE reference; raises
        :class:`PoolExhausted` (and allocates nothing) when fewer than
        ``n`` are free."""
        n = int(n)
        if n > len(self._free):
            raise PoolExhausted(
                f"asked for {n} blocks with only {len(self._free)} of "
                f"{self.num_blocks} free"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def retain(self, blocks) -> None:
        """Add one holder to each block (a prefix-cache hit mapping shared
        blocks into a new table, or the radix tree pinning a cached
        block). Retaining a block that is not live raises — a free block
        has no content worth sharing, and silently resurrecting it would
        hand a recycled page to two owners."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._ref:
                raise ValueError(
                    f"block {b} is not live (cannot retain a free/foreign block)"
                )
        for b in blocks:
            self._ref[b] += 1

    def release(self, blocks) -> None:
        """Drop one reference per block; a block whose LAST holder lets go
        returns to the free list. Releasing a block that is not live, or
        more times in one call than it has holders (double-release,
        release-below-zero, or never allocated here) raises — and releases
        NOTHING, so a bad call can never corrupt the free list or hand the
        same page to two sequences."""
        blocks = [int(b) for b in blocks]
        counts: dict[int, int] = {}
        for b in blocks:
            counts[b] = counts.get(b, 0) + 1
        for b, n in counts.items():
            if self._ref.get(b, 0) < n:
                raise ValueError(
                    f"block {b} is not live (double-freed, released below zero, "
                    "or not from this pool)"
                )
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                self._free.append(b)

    def free(self, blocks) -> None:
        """Back-compat alias of :meth:`release` — under refcounting,
        "freeing" means dropping YOUR reference; the block only reaches
        the free list when nobody else (another table, the radix tree)
        still holds it."""
        self.release(blocks)

    def swap(self, new_pools) -> None:
        """Install the jitted step's updated page arrays (the old leaves
        were donated into the step, so this is the only valid reference)."""
        self.pools = new_pools
