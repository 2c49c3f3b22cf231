"""Host-side data pipelines: sharding, batching, prefetch, interleave.

Covers the capabilities of /root/reference/dmlcloud/util/data.py:70-341, but
the architecture is a composable pipeline (tf.data / grain idiom) instead of
the reference's one-wrapper-class-per-transform stack:

- ``DataPipeline`` is the core: an epoch-aware iterator factory plus a chain
  of combinators (``shard -> batch -> map -> interleave -> prefetch ->
  to_device``). Every stage receives the epoch at iteration time, so
  ``set_epoch`` needs no per-wrapper forwarding protocol — one call on the
  pipeline re-seeds every shuffling stage.
- Batch interleaving is ONE pytree-generic implementation (arrays, dicts, or
  any nesting) with the C++ kernel (native/interleave.cpp) engaged for every
  contiguous leaf — the reference maintains two near-identical Python-loop
  variants and pins torch buffers.
- ``to_device(mesh)`` ends a pipeline on-device: batches leave as
  mesh-sharded global jax.Arrays with transfers running ahead of consumption
  (data/device.py) — the reference stops at host tensors and leaves the
  device copy to DDP/user code.

The reference's class names (``ShardedSequenceDataset``, ``ShardedXrDataset``,
``PrefetchDataset``, ``BatchDataset``, ``DownstreamDataset``) remain as thin
shims over the combinators, including torch ``DataLoader`` worker
sub-sharding via ``get_worker_info`` (effective rank = ``rank * num_workers
+ worker_id``, matching reference data.py:133-138 exactly).
"""

from __future__ import annotations

import queue as _queue
import threading
from typing import Any, Callable, Iterable, Iterator, Sequence

import numpy as np

from ..parallel import runtime
from .sharding import chunk_and_shard_indices, shard_sequence

try:  # torch is optional; used only for DataLoader interop
    from torch.utils.data import IterableDataset as _TorchIterableDataset, get_worker_info as _get_worker_info

    _DatasetBase = _TorchIterableDataset
except ImportError:  # pragma: no cover
    _DatasetBase = object

    def _get_worker_info():
        return None


def _effective_rank_world(rank: int, world_size: int) -> tuple[int, int]:
    """Sub-shard across DataLoader workers: each (rank, worker) pair becomes a
    distinct effective rank (reference data.py:131-138)."""
    info = _get_worker_info()
    if info is None:
        return rank, world_size
    return rank * info.num_workers + info.id, world_size * info.num_workers


# ---------------------------------------------------------------------------
# pipeline core
# ---------------------------------------------------------------------------

class DataPipeline(_DatasetBase):
    """An epoch-aware, composable host-data pipeline.

    Built from a ``make_iter(epoch) -> iterator`` factory; every combinator
    returns a NEW pipeline whose factory pulls from this one's, threading the
    epoch through the whole chain. Iteration state never lives on the
    pipeline object, so one pipeline can be iterated repeatedly (one pass per
    epoch — the TrainValStage contract).
    """

    def __init__(self, make_iter: Callable[[int | None], Iterator], length_fn: Callable[[], int] | None = None):
        self._make_iter = make_iter
        self._length_fn = length_fn
        #: None until set_epoch is called — sources distinguish "caller never
        #: drives epochs through this pipeline" (leave wrapped datasets'
        #: own epoch state alone) from an explicit epoch 0.
        self.epoch: int | None = None
        #: elements this pipeline's CURRENT pass has yielded — the cursor
        #: ``state_dict`` checkpoints (reset at each ``__iter__``)
        self._consumed = 0
        #: one-shot fast-forward applied by the next ``__iter__`` (set by
        #: ``load_state_dict``)
        self._pending_skip = 0

    # -- protocol -----------------------------------------------------------
    def set_epoch(self, epoch: int) -> None:
        """Re-seed every shuffling stage for this epoch (the reference's
        DistributedSampler.set_epoch analog)."""
        self.epoch = epoch

    def __iter__(self) -> Iterator:
        return self._tracked(self._make_iter(self.epoch))

    def _tracked(self, it: Iterator) -> Iterator:
        """Count yields (the resumable cursor) and apply a pending
        fast-forward. The skip REPLAYS the upstream chain and discards —
        every stateful stage (shuffle reservoirs, pack/interleave buffers,
        per-epoch RNG) re-derives its exact state deterministically, so the
        elements after the skip are bit-identical to an uninterrupted pass."""
        self._consumed = 0
        skip = self._pending_skip
        self._pending_skip = 0
        if skip:
            import itertools

            for _ in itertools.islice(it, skip):
                pass
            self._consumed = skip
        for x in it:
            self._consumed += 1
            yield x

    # -- resumable iteration state (elastic resume; doc/elasticity.md) ------
    def state_dict(self) -> dict:
        """Checkpointable iteration state: the epoch and the GLOBAL element
        offset (``local consumed x world_size`` — every rank consumes in
        lockstep, so the globally-consumed prefix is world-size-independent).
        Save it alongside the model (the stage's step-save sidecar does this
        automatically) and feed it to :meth:`load_state_dict` on resume —
        including a resume on a DIFFERENT world size, where the per-rank
        skip is re-derived from the global offset."""
        ws = runtime.world_size()
        return {
            "v": 1,
            "epoch": self.epoch,
            "global_offset": int(self._consumed) * ws,
            "world_size": ws,
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore :meth:`state_dict` output: re-seeds the epoch and arms a
        fast-forward so the next pass resumes at the exact next element. A
        global offset not divisible by the new world size cannot be resumed
        exactly (the remainder straddles ranks) — the skip rounds DOWN and
        warns, replaying at most ``world_size - 1`` global elements."""
        if not isinstance(state, dict) or state.get("v") != 1:
            raise ValueError(f"unrecognised DataPipeline state: {state!r}")
        if state.get("epoch") is not None:
            self.set_epoch(int(state["epoch"]))
        ws = runtime.world_size()
        skip, rem = divmod(int(state["global_offset"]), ws)
        if rem:
            import logging

            logging.getLogger("dmlcloud_tpu").warning(
                "DataPipeline resume: global offset %d is not divisible by the new "
                "world size %d; rounding down (up to %d element(s) replay)",
                state["global_offset"], ws, ws - 1,
            )
        self._pending_skip = skip

    def __len__(self) -> int:
        if self._length_fn is None:
            raise TypeError(f"{type(self).__name__} has no length")
        return self._length_fn()

    # -- sources ------------------------------------------------------------
    @classmethod
    def from_source(cls, iterable: Iterable) -> "DataPipeline":
        """Wrap any (re-)iterable; its ``set_epoch`` is honored if present."""

        def make(epoch: int | None) -> Iterator:
            # forward only an EXPLICIT epoch — a pipeline nobody drives must
            # not stomp an epoch the user set directly on the inner dataset
            if epoch is not None and hasattr(iterable, "set_epoch"):
                iterable.set_epoch(epoch)
            return iter(iterable)

        # length evaluated lazily — a source whose len changes after wrapping
        # (list extended before training, curriculum datasets) stays truthful
        length = (lambda: len(iterable)) if hasattr(iterable, "__len__") else None
        return cls(make, length)

    @classmethod
    def from_sequence(
        cls,
        sequence: Sequence,
        shuffle: bool = False,
        even_shards: bool = True,
        seed: int = 0,
        rank: int | None = None,
        world_size: int | None = None,
    ) -> "DataPipeline":
        """This process's share of ``sequence``, reshuffled per epoch; the
        shard is computed lazily at iteration time so torch DataLoader
        workers sub-shard correctly."""
        rank = runtime.rank() if rank is None else rank
        world_size = runtime.world_size() if world_size is None else world_size

        def make(epoch: int | None) -> Iterator:
            r, w = _effective_rank_world(rank, world_size)
            e = 0 if epoch is None else epoch
            return iter(
                shard_sequence(sequence, r, w, shuffle=shuffle, even_shards=even_shards, seed=seed + e)
            )

        def length() -> int:
            if even_shards:
                return len(sequence) // world_size
            n, rem = divmod(len(sequence), world_size)
            return n + (1 if rank < rem else 0)

        return cls(make, length)

    @classmethod
    def from_chunked(
        cls,
        ds: Any,
        dim: str,
        chunk_size: int,
        chunk_overlap: int = 0,
        even_shards: bool = True,
        equal_chunks: bool = True,
        shuffle: bool = False,
        seed: int = 0,
        rank: int | None = None,
        world_size: int | None = None,
        load: bool = False,
        load_kwargs: dict | None = None,
    ) -> "DataPipeline":
        """This process's chunks of an xarray-like (``.isel``-capable) dataset
        along ``dim`` — overlapping windows supported for time-series context
        (capability of reference data.py:70-107)."""
        rank = runtime.rank() if rank is None else rank
        world_size = runtime.world_size() if world_size is None else world_size

        def make(epoch: int | None) -> Iterator:
            r, w = _effective_rank_world(rank, world_size)
            e = 0 if epoch is None else epoch
            return _iter_chunks(
                ds, dim, chunk_size, chunk_overlap, even_shards, equal_chunks,
                shuffle, seed + e, r, w, load, load_kwargs,
            )

        return cls(make)

    # -- combinators --------------------------------------------------------
    def _chain(self, wrap: Callable[[Iterator, int], Iterator], length_fn=None) -> "DataPipeline":
        parent_make = self._make_iter
        return DataPipeline(lambda epoch: wrap(parent_make(epoch), epoch), length_fn)

    def map(self, fn: Callable[[Any], Any]) -> "DataPipeline":
        return self._chain(lambda it, _e: (fn(x) for x in it), self._length_fn)

    def pack(self, seq_len: int, *, split_long: bool = True) -> "DataPipeline":
        """Pack a stream of variable-length token sequences into fixed
        ``seq_len`` rows of ``{"tokens", "segment_ids"}`` (see
        :func:`pack_sequences`) — compose as
        ``pipeline.shuffle(...).pack(2048).batch(8)``."""
        if seq_len < 1:
            raise ValueError(f"seq_len must be >= 1, got {seq_len}")
        return self._chain(lambda it, _e: _pack_sequences_iter(it, seq_len, split_long))

    def pack_stream(
        self,
        seq_len: int,
        chunk_docs: int = 1024,
        *,
        split_long: bool = True,
        pack_window: int = 0,
        stats: "PackStats | None" = None,
    ) -> "DataPipeline":
        """Streaming chunked packing: buffer up to ``chunk_docs`` documents,
        flatten them to the two-numpy-buffer form, and hand the greedy fill
        to the C++ packer (``native.pack.pack_flat``; the Python
        ``pack_sequences`` loop when the library isn't built — bit-identical
        either way), emitting ``{"tokens", "segment_ids"}`` rows that feed
        the packed-attention path (``DecoderLM(segment_ids=...)`` +
        ``lm_loss(..., segment_ids=...)``).

        Unlike ``pack()`` (per-example Python loop) this is the production
        input path for ragged corpora: memory stays O(``chunk_docs`` docs)
        no matter how long the stream runs, and the packer works on flat
        buffers instead of per-example Python objects. The cost of
        chunking is a *boundary loss*: each chunk's final partially-filled
        row is emitted padded instead of borrowing the next chunk's first
        document, wasting at most ``seq_len - 1`` slots per chunk — a
        fraction that shrinks as ``chunk_docs`` grows. The returned
        pipeline's ``pack_stats`` (a :class:`PackStats`, live-updated
        during iteration) accounts for it: total padding-waste fraction
        and the chunk-boundary share (doc/data.md;
        tests/test_data.py::TestPackStream counts both).

        ``pack_window > 0`` switches to **window-based first-fit-decreasing
        packing** (:func:`_pack_ffd_iter`): documents are buffered in
        windows of ``pack_window``, sorted longest-first (stable — arrival
        order breaks ties), and first-fit placed into open rows that
        persist ACROSS windows, so there is no chunk-boundary tail waste
        at all — the only padding left is the end-of-stream flush and the
        slivers no remaining document fits. This reclaims most of the
        greedy packer's pad_fraction (≤ 0.10 on the pinned corpus:
        tests/test_data_store.py::TestFFDPacking::test_reclaims_greedy_padding)
        at the cost of reordering rows WITHIN a window
        horizon; the emitted row sequence is still bit-deterministic given
        the input stream and ``pack_window`` (doc/data.md, "FFD window
        semantics"). ``chunk_docs`` is ignored in this mode."""
        if seq_len < 1:
            raise ValueError(f"seq_len must be >= 1, got {seq_len}")
        if chunk_docs < 1:
            raise ValueError(f"chunk_docs must be >= 1, got {chunk_docs}")
        if pack_window < 0:
            raise ValueError(f"pack_window must be >= 0, got {pack_window}")
        st = stats if stats is not None else PackStats()

        def wrap(it: Iterator, _e) -> Iterator:
            if pack_window:
                return _pack_ffd_iter(it, seq_len, pack_window, split_long, st)
            return _pack_stream_iter(it, seq_len, chunk_docs, split_long, st)

        out = self._chain(wrap)
        out.pack_stats = st
        return out

    @classmethod
    def mix(
        cls,
        sources: Sequence["DataPipeline"],
        weights: Sequence[float] | None = None,
        seed: int = 0,
    ) -> "MixPipeline":
        """Deterministic weighted sampling over child pipelines: element
        ``t`` of the mixed stream comes from the source a counter-based
        draw — a pure function of ``(seed, t)`` — selects by cumulative
        weight. See :class:`MixPipeline` for the determinism and resume
        contract (doc/data.md)."""
        return MixPipeline(sources, weights=weights, seed=seed)

    def shuffle(self, buffer_size: int, seed: int = 0) -> "DataPipeline":
        """Streaming shuffle through a ``buffer_size`` reservoir (the
        tf.data idiom): each yield swaps a random buffer slot with the next
        upstream element, so memory stays O(buffer) on unbounded streams.
        Reshuffles per epoch via ``set_epoch`` (seed + epoch). Sequence
        sources already shuffle exactly via index permutation
        (``from_sequence(shuffle=True)``); this is for iterable sources."""
        if buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")

        def wrap(it: Iterator, epoch: int | None) -> Iterator:
            rng = np.random.default_rng(seed + (0 if epoch is None else epoch))
            buf = []
            for x in it:
                buf.append(x)
                if len(buf) == buffer_size:
                    j = rng.integers(len(buf))
                    buf[j], out = buf[-1], buf[j]
                    buf.pop()
                    yield out
            for i in rng.permutation(len(buf)):  # drain in a random order
                yield buf[i]

        return self._chain(wrap, self._length_fn)

    def batch(self, batch_size: int, drop_remainder: bool = False, collate: Callable | None = None) -> "DataPipeline":
        """Group consecutive elements into lists of ``batch_size`` (optionally
        collated, e.g. ``np.stack``)."""

        def wrap(it: Iterator, _e: int) -> Iterator:
            buf: list = []
            for x in it:
                buf.append(x)
                if len(buf) == batch_size:
                    yield collate(buf) if collate else buf
                    buf = []
            if buf and not drop_remainder:
                yield collate(buf) if collate else buf

        parent_len = self._length_fn

        def length() -> int:
            if parent_len is None:
                raise TypeError("unsized pipeline")
            n = parent_len()
            return n // batch_size if drop_remainder else -(-n // batch_size)

        return self._chain(wrap, length if parent_len is not None else None)

    def interleave(self, num_batches: int, copy: bool = True) -> "DataPipeline":
        """Re-mix groups of ``num_batches`` consecutive batches (see
        ``interleave_batches``). Batches are COPIED out of the interleave
        buffer by default, because downstream lookahead stages (``prefetch``,
        ``to_device``) hold several batches concurrently and would otherwise
        observe the buffer being rewritten by the next window. Pass
        ``copy=False`` only for a pipeline consumed strictly one batch at a
        time."""
        return self._chain(lambda it, _e: _interleave_pytrees(it, num_batches, copy=copy), self._length_fn)

    def prefetch(self, num_elements: int) -> "DataPipeline":
        """Read ahead ``num_elements`` items on a background thread, keeping
        host IO off the training thread's critical path."""
        return self._chain(lambda it, _e: _prefetch_iter(it, num_elements), self._length_fn)

    def to_device(self, mesh, pspec=None, prefetch: int = 2, host_prefetch: int = 0) -> "DataPipeline":
        """End the pipeline on-device: batches become mesh-sharded global
        jax.Arrays with ``prefetch`` transfers in flight ahead of the step;
        ``host_prefetch > 0`` additionally prepares that many host batches
        ahead on a background thread (device.py)."""
        from .device import device_iterator

        return self._chain(
            lambda it, _e: device_iterator(
                it, mesh, pspec=pspec, prefetch=prefetch, host_prefetch=host_prefetch
            ),
            self._length_fn,
        )


# ---------------------------------------------------------------------------
# streaming chunked packing (the production ragged-corpus input path)
# ---------------------------------------------------------------------------

class PackStats:
    """Live packing accounting of one ``pack_stream`` stage.

    Updated as chunks are packed (cumulative across epochs unless
    :meth:`reset` is called), readable at any point during iteration:

    - ``docs`` / ``chunks`` / ``rows``: documents consumed, chunks packed,
      fixed-shape rows emitted
    - ``tokens_in``: real tokens entering the packer
    - ``tokens_placed``: real tokens placed into rows (less than
      ``tokens_in`` only when ``split_long=False`` truncates)
    - ``slots``: ``rows * seq_len`` — every token slot emitted
    - ``pad_slots``: slots holding padding (``segment_ids == 0``)
    - ``boundary_pad_slots``: the subset of ``pad_slots`` in each chunk's
      final row — the price of never packing across a chunk boundary
    """

    def __init__(self):
        self.docs = 0
        self.chunks = 0
        self.rows = 0
        self.tokens_in = 0
        self.tokens_placed = 0
        self.slots = 0
        self.pad_slots = 0
        self.boundary_pad_slots = 0

    def reset(self) -> None:
        self.__init__()

    @property
    def pad_fraction(self) -> float:
        """Fraction of emitted slots that are padding (0.0 before any row)."""
        return self.pad_slots / self.slots if self.slots else 0.0

    @property
    def boundary_fraction(self) -> float:
        """Fraction of emitted slots wasted specifically on chunk-boundary
        tail rows — the part a larger ``chunk_docs`` would reclaim."""
        return self.boundary_pad_slots / self.slots if self.slots else 0.0

    def as_dict(self) -> dict:
        return {
            "docs": self.docs,
            "chunks": self.chunks,
            "rows": self.rows,
            "tokens_in": self.tokens_in,
            "tokens_placed": self.tokens_placed,
            "slots": self.slots,
            "pad_slots": self.pad_slots,
            "boundary_pad_slots": self.boundary_pad_slots,
            "pad_fraction": round(self.pad_fraction, 6),
            "boundary_fraction": round(self.boundary_fraction, 6),
        }

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"PackStats({self.as_dict()})"


def _pack_stream_iter(docs: Iterator, seq_len: int, chunk_docs: int, split_long: bool, stats: PackStats) -> Iterator[dict]:
    """Chunked packing core: per window of ``chunk_docs`` documents, one
    flatten + one native ``pack_flat`` call (Python packer fallback —
    bit-identical, asserted in tests), rows yielded one at a time so
    downstream stages stream. Each chunk packs independently; the
    resulting per-chunk rows are exactly ``pack_sequences(chunk)``."""
    try:
        from ..native import pack as _native_pack

        native_ok = _native_pack.available()
    except Exception:  # pragma: no cover - import guard
        _native_pack, native_ok = None, False

    def pack_chunk(buf: list) -> Iterator[dict]:
        arrays = [np.asarray(d, np.int32).ravel() for d in buf]
        stats.docs += len(arrays)
        arrays = [a for a in arrays if a.size]  # the packer skips empty docs
        if not arrays:
            return
        n_in = sum(int(a.size) for a in arrays)
        stats.tokens_in += n_in
        if native_ok:
            lengths = np.fromiter((a.size for a in arrays), np.int64, count=len(arrays))
            flat = np.concatenate(arrays)
            tokens, segs = _native_pack.pack_flat(flat, lengths, seq_len, split_long=split_long)
            rows = [{"tokens": tokens[i], "segment_ids": segs[i]} for i in range(len(tokens))]
        else:
            rows = list(_pack_sequences_iter(arrays, seq_len, split_long))
        stats.chunks += 1
        stats.rows += len(rows)
        stats.slots += len(rows) * seq_len
        pad = sum(int(np.count_nonzero(r["segment_ids"] == 0)) for r in rows)
        stats.pad_slots += pad
        stats.tokens_placed += len(rows) * seq_len - pad
        if rows:
            stats.boundary_pad_slots += int(np.count_nonzero(rows[-1]["segment_ids"] == 0))
        yield from rows

    buf: list = []
    for doc in docs:
        buf.append(doc)
        if len(buf) == chunk_docs:
            yield from pack_chunk(buf)
            buf = []
    if buf:
        yield from pack_chunk(buf)


def _pack_ffd_iter(docs: Iterator, seq_len: int, window_docs: int, split_long: bool, stats: PackStats) -> Iterator[dict]:
    """Window-based first-fit-decreasing packing (``pack_stream(...,
    pack_window=N)``).

    Documents buffer in windows of ``window_docs``; each window is sorted
    longest-first (stable — equal lengths keep arrival order) and first-fit
    placed into open rows ("bins"). Unlike the chunked greedy packer, bins
    are NOT flushed at window boundaries: a partially-filled row stays open
    for the next window's documents, so the chunk-boundary tail waste
    disappears entirely — the only padding left is (a) slivers no remaining
    document fits and (b) the end-of-stream flush, which is the only place
    this packer adds to ``boundary_pad_slots``.

    Rows are emitted the moment they fill (or when the open-bin cap — ``max
    (window_docs, 16)`` — evicts the fullest, oldest-first bin to bound
    memory), so downstream stages stream. Everything is pure sequential
    bookkeeping over the input order: the emitted row sequence is
    bit-deterministic given (input stream, ``seq_len``, ``window_docs``).
    """
    max_open = max(int(window_docs), 16)
    bins: list[list] = []  # [fill, parts]; list order == creation order == first-fit order

    def emit(parts: list, fill: int, boundary: bool = False) -> dict:
        tokens = np.zeros(seq_len, np.int32)
        segs = np.zeros(seq_len, np.int32)
        at = 0
        for seg, p in enumerate(parts, 1):
            tokens[at : at + p.size] = p
            segs[at : at + p.size] = seg
            at += p.size
        stats.rows += 1
        stats.slots += seq_len
        stats.pad_slots += seq_len - fill
        stats.tokens_placed += fill
        if boundary:
            stats.boundary_pad_slots += seq_len - fill
        return {"tokens": tokens, "segment_ids": segs}

    def place(part: np.ndarray) -> dict | None:
        for b in bins:
            if b[0] + part.size <= seq_len:
                b[1].append(part)
                b[0] += part.size
                if b[0] == seq_len:
                    bins.remove(b)
                    return emit(b[1], b[0])
                return None
        bins.append([int(part.size), [part]])
        if len(bins) > max_open:
            # bound memory: close the fullest bin (ties -> oldest); its
            # padding is ordinary waste, not boundary waste
            full = max(bins, key=lambda b: b[0])
            bins.remove(full)
            return emit(full[1], full[0])
        return None

    def run_window(buf: list) -> Iterator[dict]:
        arrays = [np.asarray(d, np.int32).ravel() for d in buf]
        stats.docs += len(arrays)
        arrays = [a for a in arrays if a.size]
        if not arrays:
            return
        stats.tokens_in += sum(int(a.size) for a in arrays)
        stats.chunks += 1
        parts: list[np.ndarray] = []
        for a in arrays:
            if a.size > seq_len:
                if split_long:
                    # whole seq_len pieces are born full rows; the tail
                    # joins the window's FFD pool like any short document
                    off = 0
                    while a.size - off >= seq_len:
                        yield emit([a[off : off + seq_len]], seq_len)
                        off += seq_len
                    if off < a.size:
                        parts.append(a[off:])
                else:
                    yield emit([a[:seq_len]], seq_len)
            else:
                parts.append(a)
        parts.sort(key=lambda p: p.size, reverse=True)  # stable: ties keep arrival order
        for p in parts:
            row = place(p)
            if row is not None:
                yield row

    buf: list = []
    for doc in docs:
        buf.append(doc)
        if len(buf) == window_docs:
            yield from run_window(buf)
            buf = []
    if buf:
        yield from run_window(buf)
    for fill, parts in bins:  # end-of-stream flush: the only boundary waste
        yield emit(parts, fill, boundary=True)


# ---------------------------------------------------------------------------
# deterministic weighted multi-source mixing
# ---------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def _mix_u64(seed: int, step: int) -> int:
    """splitmix64-style counter hash: a uniform u64 that is a pure function
    of ``(seed, step)`` — no RNG object, no hidden state, so the draw
    sequence can be re-entered at any step (elastic resume) and is
    identical on every rank and platform."""
    x = (int(seed) * 0x9E3779B97F4A7C15 + (int(step) + 1) * 0xD1B54A32D192ED03) & _M64
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def _mix_choice(seed: int, step: int, weights: Sequence[float], alive: Sequence[bool]) -> int:
    """Source index for draw ``step``: the u64 mapped onto the cumulative
    weights of the still-alive sources (exhausted sources renormalize away
    by carrying zero mass)."""
    total = sum(w for w, a in zip(weights, alive) if a)
    u = (_mix_u64(seed, step) / float(1 << 64)) * total
    acc = 0.0
    last = 0
    for i, (w, a) in enumerate(zip(weights, alive)):
        if not a:
            continue
        acc += w
        last = i
        if u < acc:
            return i
    return last  # float roundoff on the final boundary


class MixPipeline(DataPipeline):
    """Deterministic weighted mixing over child pipelines
    (``DataPipeline.mix``).

    The choice sequence is a pure function of ``(seed, draw index)``
    (counter-based splitmix64 — no RNG object), so the mix is reproducible
    run-to-run and resumable mid-stream: ``state_dict`` captures the draw
    cursor plus every child's own PR-7 iterator state, and
    ``load_state_dict`` fast-forwards the children and re-enters the draw
    sequence at the exact next step — 0 replayed and 0 skipped samples,
    including across a world-size change (all cursors are stored as
    world-size-independent global offsets). A source that exhausts
    renormalizes the remaining weights with a logged warning; the mix ends
    when every source is exhausted."""

    def __init__(
        self,
        sources: Sequence[DataPipeline],
        weights: Sequence[float] | None = None,
        seed: int = 0,
    ):
        sources = list(sources)
        if not sources:
            raise ValueError("mix needs at least one source")
        if weights is None:
            weights = [1.0] * len(sources)
        weights = [float(w) for w in weights]
        if len(weights) != len(sources):
            raise ValueError(
                f"mix got {len(sources)} source(s) but {len(weights)} weight(s)"
            )
        if any(not np.isfinite(w) or w <= 0 for w in weights):
            raise ValueError(f"mix weights must be positive and finite, got {weights}")
        self._sources = sources
        self._weights = weights
        self._seed = int(seed)
        #: draws made by the CURRENT pass / carried in from a resume
        self._draws = 0
        self._draws_base = 0
        #: elements the pass resumed past (load_state_dict arms it)
        self._consumed_base = 0
        self._exhausted = [False] * len(sources)
        #: one-shot resume payload applied by the next __iter__
        self._mix_resume: dict | None = None

        def length() -> int:
            return sum(len(s) for s in self._sources)

        super().__init__(self._mix_iter, length)

    # every shuffling stage of every child re-seeds together
    def set_epoch(self, epoch: int) -> None:
        super().set_epoch(epoch)
        for s in self._sources:
            if hasattr(s, "set_epoch"):
                s.set_epoch(epoch)

    def _mix_iter(self, epoch) -> Iterator:
        # epoch folds into the seed (the shuffle() convention): each epoch
        # draws a fresh deterministic choice sequence, and a mid-epoch
        # resume re-derives the same one (state_dict carries the epoch)
        seed = self._seed + (0 if epoch is None else int(epoch))
        resume = self._mix_resume
        self._mix_resume = None
        if resume is None:
            self._draws_base = 0
            self._consumed_base = 0
            alive = [True] * len(self._sources)
        else:
            self._draws_base = resume["draws"]
            self._consumed_base = resume["consumed"]
            alive = [not x for x in resume["exhausted"]]
        self._draws = 0
        self._exhausted = [not a for a in alive]
        its = [iter(s) for s in self._sources]
        while True:
            live = [w for w, a in zip(self._weights, alive) if a]
            if not live:
                return
            i = _mix_choice(seed, self._draws_base + self._draws, self._weights, alive)
            self._draws += 1
            try:
                yield next(its[i])
            except StopIteration:
                alive[i] = False
                self._exhausted[i] = True
                if any(alive):
                    import logging

                    remaining = [w for w, a in zip(self._weights, alive) if a]
                    logging.getLogger("dmlcloud_tpu").warning(
                        "mix: source %d exhausted after %d draw(s); renormalizing "
                        "over the %d remaining source(s) (weights %s)",
                        i, self._draws_base + self._draws, len(remaining), remaining,
                    )
                continue

    # -- resumable iteration state (doc/data.md, doc/elasticity.md) ---------
    def state_dict(self) -> dict:
        """The mix cursor — global element offset AND global draw count
        (draws outnumber yields when a draw hit an exhausted source) — plus
        every child's own iterator state. All counters are global
        (``local x world_size``), so a resume on a different world size
        re-derives its per-rank position exactly like the base class."""
        ws = runtime.world_size()
        return {
            "v": 1,
            "kind": "mix",
            "epoch": self.epoch,
            "global_offset": (self._consumed_base + self._consumed) * ws,
            "global_draws": (self._draws_base + self._draws) * ws,
            "world_size": ws,
            "exhausted": list(self._exhausted),
            "children": [s.state_dict() for s in self._sources],
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a mix ``state_dict``: children fast-forward through their
        OWN ``load_state_dict`` (no replay through the mix), and the next
        pass re-enters the draw sequence at the saved step. A plain
        (non-mix) v1 state degrades to the base class's replay skip — the
        draws are pure in ``(seed, step)``, so replay reproduces the exact
        same choices."""
        if not (isinstance(state, dict) and state.get("kind") == "mix"):
            super().load_state_dict(state)
            return
        if state.get("v") != 1:
            raise ValueError(f"unrecognised MixPipeline state: {state!r}")
        children = state.get("children") or []
        if len(children) != len(self._sources):
            raise ValueError(
                f"mix state carries {len(children)} child state(s) for "
                f"{len(self._sources)} source(s)"
            )
        for s, cs in zip(self._sources, children):
            s.load_state_dict(cs)
        if state.get("epoch") is not None:
            self.set_epoch(int(state["epoch"]))
        ws = runtime.world_size()
        consumed, rem_c = divmod(int(state["global_offset"]), ws)
        draws, rem_d = divmod(int(state["global_draws"]), ws)
        if rem_c or rem_d:
            import logging

            logging.getLogger("dmlcloud_tpu").warning(
                "mix resume: global cursor (%d elements, %d draws) is not divisible "
                "by the new world size %d; rounding down",
                state["global_offset"], state["global_draws"], ws,
            )
        self._pending_skip = 0  # children fast-forward themselves
        self._mix_resume = {
            "consumed": consumed,
            "draws": draws,
            "exhausted": [bool(x) for x in state.get("exhausted", [])]
            or [False] * len(self._sources),
        }

def _iter_chunks(
    ds, dim, chunk_size, chunk_overlap, even_shards, equal_chunks, shuffle, seed, rank, world_size, load, load_kwargs
) -> Iterator[Any]:
    num_elements = len(ds[dim]) if hasattr(ds, "__getitem__") and not isinstance(ds, np.ndarray) else ds.sizes[dim]
    chunks = chunk_and_shard_indices(
        num_elements, chunk_size, rank, world_size,
        chunk_overlap=chunk_overlap, even_shards=even_shards, equal_chunks=equal_chunks,
        shuffle=shuffle, seed=seed,
    )
    for start, end in chunks:
        chunk = ds.isel({dim: slice(start, end)})
        if load:
            chunk.load(**(load_kwargs or {}))
        yield chunk


def _prefetch_iter(src: Iterator, num_elements: int, name: str = "dml-host-prefetch") -> Iterator:
    """Bounded-queue background reader. Exceptions in the source re-raise in
    the consumer; closing/abandoning the consumer generator signals the
    producer to stop (otherwise it would block forever on a full queue,
    pinning the thread, its queued batches, and the source iterator).
    ``name`` labels the producer thread (``ShardReader`` reuses this
    machinery under ``dml-shard-reader``)."""
    q: _queue.Queue = _queue.Queue(maxsize=max(num_elements, 1))
    stop = threading.Event()
    _END, _ERR = object(), object()

    def put(item: Any) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except _queue.Full:
                continue
        return False

    def produce() -> None:
        try:
            for item in src:
                if not put(item):
                    return
        except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
            put((_ERR, e))
            return
        put(_END)

    # named so shutdown tests (and a forensics dump's thread list) can
    # identify host-prefetch threads; daemon so a full queue can never pin
    # process exit even if the consumer leaks the generator
    thread = threading.Thread(target=produce, daemon=True, name=name)
    thread.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
                raise item[1]
            yield item
    finally:
        stop.set()
        try:  # free one slot so a put-blocked producer observes stop promptly
            q.get_nowait()
        except _queue.Empty:
            pass


# ---------------------------------------------------------------------------
# batch interleaving (pytree-generic, native-accelerated)
# ---------------------------------------------------------------------------

def _interleave_pytrees(iterable: Iterable[Any], num_batches: int, copy: bool = False) -> Iterator[Any]:
    """Re-slice each window of ``num_batches`` consecutive batches into
    ``num_batches`` mixed batches, per pytree leaf, through preallocated
    buffers. Mixed batch ``i`` is the concatenation of slice ``i`` of every
    window batch — restores within-batch diversity when upstream chunked
    reads (e.g. xarray time chunks) make batches internally correlated.

    Leaves are interleaved by the C++ kernel (native/interleave.cpp) when
    contiguous, else by strided numpy copies. Yielded leaves ALIAS the reused
    buffers: consume or copy before advancing.
    """
    import jax

    if num_batches < 1:
        raise ValueError("num_batches must be greater than 0")
    if num_batches == 1:
        yield from iterable
        return

    try:
        from ..native import interleave as _native

        native_ok = _native.available()
    except Exception:  # pragma: no cover
        _native, native_ok = None, False

    treedef = None
    buffers: list[np.ndarray] = []
    slice_sizes: list[int] = []
    window: list[list[np.ndarray]] = []

    for batch in iterable:
        leaves, this_def = jax.tree_util.tree_flatten(batch)
        leaves = [np.asarray(x) for x in leaves]
        if treedef is None:
            treedef = this_def
            for leaf in leaves:
                if leaf.shape[0] % num_batches:
                    raise ValueError(
                        f"Batch dimension ({leaf.shape[0]}) must be divisible by num_batches={num_batches}"
                    )
                slice_sizes.append(leaf.shape[0] // num_batches)
                buffers.append(np.empty((num_batches, *leaf.shape), dtype=leaf.dtype))

        window.append(leaves)
        if len(window) < num_batches:
            continue

        for li, (buf, s) in enumerate(zip(buffers, slice_sizes)):
            srcs = [w[li] for w in window]
            if native_ok and all(b.flags.c_contiguous for b in srcs):
                _native.interleave_into(buf, srcs, s)
            else:
                for i in range(num_batches):
                    for j in range(num_batches):
                        buf[i, j * s : (j + 1) * s] = srcs[j][i * s : (i + 1) * s]
        window = []
        for i in range(num_batches):
            leaves_out = [buf[i].copy() if copy else buf[i] for buf in buffers]
            yield jax.tree_util.tree_unflatten(treedef, leaves_out)


def interleave_batches(iterable: Iterable[np.ndarray], num_batches: int) -> Iterator[np.ndarray]:
    """Array variant (capability of reference data.py:266-301). Yielded views
    alias a reused buffer — consume or copy immediately."""
    return _interleave_pytrees(iterable, num_batches)


def interleave_dict_batches(
    iterable: Iterable[dict[str, np.ndarray]], num_batches: int
) -> Iterator[dict[str, np.ndarray]]:
    """Dict-of-arrays variant (capability of reference data.py:304-341) —
    same pytree core, same C++ fast path. Yielded dicts alias reused buffers."""
    return _interleave_pytrees(iterable, num_batches)


# ---------------------------------------------------------------------------
# reference-parity shims (class API of dmlcloud.util.data)
# ---------------------------------------------------------------------------

class _ReconstructOnUnpickle:
    """The pipeline core holds closures, which do not pickle; the shims must
    pickle because torch DataLoader workers receive the dataset by pickle.
    Each shim records its constructor arguments and is rebuilt (epoch
    preserved) on the other side."""

    _ctor_args: tuple = ()
    _ctor_kwargs: dict = {}

    def __getstate__(self):
        return {"args": self._ctor_args, "kwargs": self._ctor_kwargs, "epoch": self.epoch}

    def __setstate__(self, state):
        self.__init__(*state["args"], **state["kwargs"])
        self.epoch = state["epoch"]

def sharded_xr_dataset(
    ds: Any,
    dim: str,
    chunk_size: int,
    chunk_overlap: int = 0,
    even_shards: bool = True,
    equal_chunks: bool = True,
    shuffle: bool = False,
    seed: int = 0,
    rank: int | None = None,
    world_size: int | None = None,
    load: bool = False,
    load_kwargs: dict | None = None,
) -> Iterator[Any]:
    """One epoch of per-rank chunks of an ``.isel``-capable dataset
    (reference data.py:70-107)."""
    rank = runtime.rank() if rank is None else rank
    world_size = runtime.world_size() if world_size is None else world_size
    return _iter_chunks(
        ds, dim, chunk_size, chunk_overlap, even_shards, equal_chunks,
        shuffle, seed, rank, world_size, load, load_kwargs,
    )


class ShardedSequenceDataset(_ReconstructOnUnpickle, DataPipeline):
    """Reference-parity shim over ``DataPipeline.from_sequence``
    (reference data.py:110-147)."""

    def __init__(
        self,
        sequence: Sequence,
        shuffle: bool = False,
        even_shards: bool = True,
        seed: int = 0,
        rank: int | None = None,
        world_size: int | None = None,
    ):
        rank = runtime.rank() if rank is None else rank
        world_size = runtime.world_size() if world_size is None else world_size
        self._ctor_args = (sequence, shuffle, even_shards, seed, rank, world_size)
        self._ctor_kwargs = {}
        p = DataPipeline.from_sequence(
            sequence, shuffle=shuffle, even_shards=even_shards, seed=seed, rank=rank, world_size=world_size
        )
        super().__init__(p._make_iter, p._length_fn)
        self.sequence = sequence


class ShardedXrDataset(_ReconstructOnUnpickle, DataPipeline):
    """Reference-parity shim over ``DataPipeline.from_chunked``; the full
    positional parameter order matches reference data.py:150-207 including
    the ``process_group`` slot (meaningless here — JAX has one global
    runtime — but kept so positional callers' ``load``/``load_kwargs``
    don't silently shift)."""

    def __init__(
        self,
        ds: Any,
        dim: str,
        chunk_size: int,
        chunk_overlap: int = 0,
        even_shards: bool = True,
        equal_chunks: bool = True,
        shuffle: bool = False,
        seed: int = 0,
        rank: int | None = None,
        world_size: int | None = None,
        process_group: Any = None,
        load: bool = False,
        load_kwargs: dict | None = None,
    ):
        if process_group is not None:
            raise ValueError(
                "process_group is a torch.distributed concept; the JAX runtime has a "
                "single global process group — pass rank/world_size instead"
            )
        rank = runtime.rank() if rank is None else rank
        world_size = runtime.world_size() if world_size is None else world_size
        self._ctor_args = (ds, dim, chunk_size, chunk_overlap, even_shards, equal_chunks,
                           shuffle, seed, rank, world_size, None, load, load_kwargs)
        self._ctor_kwargs = {}
        p = DataPipeline.from_chunked(
            ds, dim, chunk_size, chunk_overlap=chunk_overlap, even_shards=even_shards,
            equal_chunks=equal_chunks, shuffle=shuffle, seed=seed, rank=rank,
            world_size=world_size, load=load, load_kwargs=load_kwargs,
        )
        super().__init__(p._make_iter, p._length_fn)
        self.ds = ds


class DownstreamDataset(_ReconstructOnUnpickle, DataPipeline):
    """Reference-parity base for wrappers (reference data.py:210-219):
    epoch setting propagates to the wrapped source."""

    def __init__(self, source_ds: Iterable):
        self._ctor_args = (source_ds,)
        self._ctor_kwargs = {}
        p = DataPipeline.from_source(source_ds)
        super().__init__(p._make_iter, p._length_fn)
        self.source_ds = source_ds

    def set_epoch(self, epoch: int) -> None:
        super().set_epoch(epoch)
        if hasattr(self.source_ds, "set_epoch"):
            self.source_ds.set_epoch(epoch)


class PrefetchDataset(DownstreamDataset):
    """Reference-parity shim over ``.prefetch()`` (reference data.py:222-240)."""

    def __init__(self, source_ds: Iterable, num_elements: int):
        super().__init__(source_ds)
        self._ctor_args = (source_ds, num_elements)
        self.num_elements = num_elements
        parent = self._make_iter
        self._make_iter = lambda epoch: _prefetch_iter(parent(epoch), num_elements)


class BatchDataset(DownstreamDataset):
    """Reference-parity shim over ``.batch()`` (reference data.py:243-263)."""

    def __init__(self, source_ds: Iterable, batch_size: int, drop_remainder: bool = False):
        super().__init__(source_ds)
        self._ctor_args = (source_ds, batch_size, drop_remainder)
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder
        batched = DataPipeline(self._make_iter, self._length_fn).batch(batch_size, drop_remainder)
        self._make_iter = batched._make_iter
        self._length_fn = batched._length_fn


def pack_sequences(
    examples: Iterable[Sequence[int] | np.ndarray],
    seq_len: int,
    *,
    split_long: bool = True,
) -> Iterator[dict]:
    """Greedily pack variable-length token sequences into fixed ``seq_len``
    rows, yielding ``{"tokens": [seq_len] int32, "segment_ids": [seq_len]
    int32}`` — the input contract of ``DecoderLM(segment_ids=...)`` /
    ``lm_loss(segment_ids=...)`` (models/transformer.py): segment ids are
    1-based per row, 0 marks padding, attention never crosses a segment
    boundary and positions restart per segment.

    Streaming single-pass fill: an example that fits the remaining row space
    is appended whole; one that fits an EMPTY row starts a fresh row (never
    split — a split would sever intra-example attention and break the
    packed-equals-unpacked equivalence); only examples longer than
    ``seq_len`` itself are split across rows when ``split_long`` (each part
    its own segment — no cross-row attention), else truncated to
    ``seq_len``. The trailing partially-filled row is emitted padded. (The
    reference has no packing; this is TPU-side scope — static shapes
    without burning FLOPs on padding.)
    """
    if seq_len < 1:  # validate eagerly — the generator body runs lazily
        raise ValueError(f"seq_len must be >= 1, got {seq_len}")
    return _pack_sequences_iter(examples, seq_len, split_long)


def _pack_sequences_iter(examples, seq_len, split_long):
    tokens = np.zeros(seq_len, np.int32)
    segs = np.zeros(seq_len, np.int32)
    fill, seg = 0, 0

    def flush():
        nonlocal tokens, segs, fill, seg
        out = {"tokens": tokens, "segment_ids": segs}
        tokens, segs = np.zeros(seq_len, np.int32), np.zeros(seq_len, np.int32)
        fill, seg = 0, 0
        return out

    def place(part):
        nonlocal fill, seg
        seg += 1
        tokens[fill : fill + part.size] = part
        segs[fill : fill + part.size] = seg
        fill += part.size

    for ex in examples:
        ex = np.asarray(ex, np.int32).ravel()
        if ex.size == 0:
            continue
        if ex.size <= seq_len:
            if ex.size > seq_len - fill:
                yield flush()
            place(ex)
            if fill == seq_len:
                yield flush()
        elif split_long:
            offset = 0
            while offset < ex.size:
                if fill == seq_len:
                    yield flush()
                take = min(ex.size - offset, seq_len - fill)
                place(ex[offset : offset + take])
                offset += take
        else:
            if fill:
                yield flush()
            place(ex[:seq_len])
            yield flush()
    if fill:
        yield flush()
