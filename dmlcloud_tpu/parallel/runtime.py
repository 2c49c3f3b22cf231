"""L1 distributed runtime: one-call cluster bootstrap + control-plane collectives.

Capability parity with /root/reference/dmlcloud/util/distributed.py (the
``init_process_group_*`` ladder at :142-244, rank accessors :84-101, root
helpers :43-70, object collectives :121-139, deinit :247-259) — re-designed for
JAX's multi-controller runtime:

- ``torch.distributed`` process groups -> one ``jax.distributed.initialize()``
  control plane (gRPC coordination service over DCN) plus XLA collectives over
  ICI for tensor traffic.
- c10d TCPStore/HashStore rendezvous -> the jax.distributed coordinator; the
  Slurm / MPI / env-var / single-process detection ladder is preserved in
  spirit (the reference's four init paths map 1:1 onto the four ``init_*``
  functions below).
- gloo object collectives -> the coordination-service key-value store
  (rendezvous-grade small payloads, never touching device memory or ICI).
- ``monitored_barrier`` -> ``wait_at_barrier`` on the coordination client,
  which has real timeout semantics and names the barrier that timed out.

Single-process use (the reference's ``init_process_group_dummy``,
util/distributed.py:142-159) requires no initialization at all — every
accessor and collective degenerates correctly — but ``init_single()`` exists
so user code can call ``init_auto()`` unconditionally.
"""

from __future__ import annotations

import base64
import functools
import logging
import os
import pickle
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from ..utils import slurm as _slurm
from ..utils.tcp import find_free_port, get_local_ips

logger = logging.getLogger("dmlcloud_tpu")

#: Default coordinator port; analog of the reference's DEFAULT_PORT=41312
#: (util/distributed.py:10), overridable via env.
DEFAULT_PORT = int(os.environ.get("DMLCLOUD_TPU_PORT", 41313))

_DEFAULT_TIMEOUT = 600.0  # seconds; matches the reference's 10-min barriers (pipeline.py:244)


@dataclass
class _WorkerInfo:
    """Cached process-level topology, set once at init (reference: the
    ``_WorkerInfo`` global at util/distributed.py:13-19)."""

    rank: int = 0
    world_size: int = 1
    local_rank: int = 0
    local_world_size: int = 1
    node: int = 0
    initialized: bool = False
    backend: str = "single"


_info = _WorkerInfo()


# ---------------------------------------------------------------------------
# predicates
# ---------------------------------------------------------------------------

def is_initialized() -> bool:
    """True once any ``init_*`` path has run."""
    return _info.initialized


def has_slurm() -> bool:
    """True inside a Slurm step (reference util/distributed.py:22-23)."""
    return _slurm.slurm_available()


def has_mpi() -> bool:
    """True if mpi4py is importable (reference util/distributed.py:30-36)."""
    try:
        import mpi4py  # noqa: F401

        return True
    except ImportError:
        return False


def has_environment() -> bool:
    """True if an explicit coordinator address is provided via env — the analog
    of the reference's MASTER_PORT probe (util/distributed.py:26-27)."""
    return "DMLCLOUD_TPU_COORDINATOR" in os.environ or "JAX_COORDINATOR_ADDRESS" in os.environ


def has_tpu_pod_env() -> bool:
    """True on a multi-host Cloud TPU pod slice, where libtpu metadata gives
    jax.distributed everything it needs with zero arguments."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


# ---------------------------------------------------------------------------
# rank accessors (reference util/distributed.py:84-101)
# ---------------------------------------------------------------------------

def rank() -> int:
    """Process rank (multi-controller index). NOTE: in JAX each process owns
    several devices; use ``device_rank``/``device_count`` for per-chip ids."""
    return _info.rank if _info.initialized else jax.process_index()


def world_size() -> int:
    """Number of controller processes."""
    return _info.world_size if _info.initialized else jax.process_count()


def local_rank() -> int:
    return _info.local_rank


def local_world_size() -> int:
    return _info.local_world_size


def local_node() -> int:
    return _info.node


def device_count() -> int:
    """Global number of accelerator devices (chips), across all processes."""
    return jax.device_count()


def local_device_count() -> int:
    return jax.local_device_count()


def is_root() -> bool:
    return rank() == 0


# ---------------------------------------------------------------------------
# root helpers (reference util/distributed.py:43-70)
# ---------------------------------------------------------------------------

def root_only(fn: Callable) -> Callable:
    """Decorator: run only on the root process; other ranks return None."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if is_root():
            return fn(*args, **kwargs)
        return None

    return wrapper


@contextmanager
def root_first():
    """Context manager: the root process executes the body first, then all
    other ranks enter after a barrier (reference util/distributed.py:55-70).
    Canonical use: dataset download."""
    if is_root():
        try:
            yield
        finally:
            barrier("root_first")
    else:
        barrier("root_first")
        yield


def print_root(*args, **kwargs) -> None:
    if is_root():
        print(*args, **kwargs)


def print_worker(*args, flush: bool = True, barrier_first: bool = False, **kwargs) -> None:
    """Print prefixed with the worker rank (reference util/distributed.py:104-112)."""
    if barrier_first:
        barrier("print_worker")
    print(f"Worker {rank()} ({local_node()}.{local_rank()}):", *args, flush=flush, **kwargs)


# ---------------------------------------------------------------------------
# init ladder (reference util/distributed.py:142-244)
# ---------------------------------------------------------------------------

def _cpu_safety_flags() -> None:
    """Disable async dispatch on the CPU backend (no effect on TPU).

    XLA:CPU shares one small thread pool across all (virtual) devices; with
    async dispatch, many in-flight programs containing collectives starve the
    40s collective rendezvous and hard-abort the process on few-core machines
    (the CI/emulation environment this backend exists for). Must run before
    the CPU client is instantiated — which is why every ``init_*`` path calls
    it first.
    """
    jax.config.update("jax_cpu_enable_async_dispatch", False)


def init_single() -> None:
    """Single-process fallback — the analog of ``init_process_group_dummy``
    (reference util/distributed.py:142-159). No coordination service is
    started; all collectives degenerate to identity."""
    _cpu_safety_flags()
    _info.rank = 0
    _info.world_size = 1
    _info.local_rank = 0
    _info.local_world_size = 1
    _info.node = 0
    _info.backend = "single"
    _info.initialized = True


def init_from_env(**kwargs) -> None:
    """Init from an explicit coordinator address in the environment — the
    analog of the ``env://`` torchrun path (reference util/distributed.py:237-238).

    Env contract: ``DMLCLOUD_TPU_COORDINATOR=host:port`` (or JAX's own
    ``JAX_COORDINATOR_ADDRESS``), ``DMLCLOUD_TPU_NUM_PROCESSES``,
    ``DMLCLOUD_TPU_PROCESS_ID`` (fall back to JAX's env vars, then to 1/0).
    """
    _cpu_safety_flags()
    coordinator = os.environ.get("DMLCLOUD_TPU_COORDINATOR") or os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = int(os.environ.get("DMLCLOUD_TPU_NUM_PROCESSES") or os.environ.get("JAX_NUM_PROCESSES") or 1)
    pid = int(os.environ.get("DMLCLOUD_TPU_PROCESS_ID") or os.environ.get("JAX_PROCESS_ID") or 0)
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=nproc, process_id=pid, **kwargs
    )
    _fill_info(pid, nproc, backend="env")


def init_tpu_pod(**kwargs) -> None:
    """Init on a Cloud TPU pod slice: libtpu metadata supplies coordinator,
    process count and id, so ``jax.distributed.initialize()`` is argument-free."""
    _cpu_safety_flags()
    jax.distributed.initialize(**kwargs)
    _fill_info(jax.process_index(), jax.process_count(), backend="tpu_pod")


def init_slurm(port: int = DEFAULT_PORT, **kwargs) -> None:
    """Init from Slurm env vars — analog of ``init_process_group_slurm``
    (reference util/distributed.py:162-177): rank/world from
    SLURM_{PROCID,NTASKS,...}, coordinator = first node of the allocation."""
    _cpu_safety_flags()
    rank_ = _slurm.slurm_rank()
    world = _slurm.slurm_world_size()
    head = _slurm.slurm_head_node()
    if rank_ is None or world is None or head is None:
        raise RuntimeError("Slurm environment incomplete (need SLURM_PROCID/SLURM_NTASKS/nodelist)")
    jax.distributed.initialize(
        coordinator_address=f"{head}:{port}", num_processes=world, process_id=rank_, **kwargs
    )
    _fill_info(
        rank_,
        world,
        local_rank=_slurm.slurm_local_rank() or 0,
        local_world=_slurm.slurm_tasks_per_node() or 1,
        node=_slurm.slurm_node_id() or 0,
        backend="slurm",
    )


def init_mpi(**kwargs) -> None:
    """Init via MPI address exchange — analog of ``init_process_group_MPI``
    (reference util/distributed.py:180-224): MPI gives rank/size; the root
    picks a free port + routable IP and broadcasts them; jax.distributed then
    rendezvouses on that address. MPI is used ONLY for the address exchange."""
    _cpu_safety_flags()
    from mpi4py import MPI

    comm = MPI.COMM_WORLD
    rank_, world = comm.Get_rank(), comm.Get_size()
    local_comm = comm.Split_type(MPI.COMM_TYPE_SHARED)
    ip, port = None, None
    if rank_ == 0:
        port = find_free_port()
        ip = get_local_ips()[0]
    ip = comm.bcast(ip, root=0)
    port = comm.bcast(port, root=0)
    comm.Barrier()
    jax.distributed.initialize(
        coordinator_address=f"{ip}:{port}", num_processes=world, process_id=rank_, **kwargs
    )
    _fill_info(
        rank_,
        world,
        local_rank=local_comm.Get_rank(),
        local_world=local_comm.Get_size(),
        node=rank_ // max(local_comm.Get_size(), 1),
        backend="mpi",
    )


def init_auto(verbose: bool = False, **kwargs) -> str:
    """Detect the launch environment and initialize the right way — the analog
    of ``init_process_group_auto`` (reference util/distributed.py:227-244).

    Ladder: explicit env coordinator -> Cloud TPU pod metadata -> Slurm ->
    MPI -> single process. Returns the chosen backend name.
    """
    if _info.initialized:
        return _info.backend
    if has_environment():
        init_from_env(**kwargs)
    elif has_tpu_pod_env():
        init_tpu_pod(**kwargs)
    elif has_slurm():
        init_slurm(**kwargs)
    elif has_mpi():
        init_mpi(**kwargs)
    else:
        init_single()
    if verbose:
        logger.info(f"initialized distributed runtime via '{_info.backend}' "
                    f"(rank {rank()}/{world_size()}, {local_device_count()} local devices)")
    return _info.backend


def _fill_info(rank_: int, world: int, local_rank: int = 0, local_world: int = 1,
               node: int = 0, backend: str = "env") -> None:
    _info.rank = rank_
    _info.world_size = world
    _info.local_rank = local_rank
    _info.local_world_size = local_world
    _info.node = node
    _info.backend = backend
    _info.initialized = True


def deinitialize() -> None:
    """Tear the runtime down (reference ``deinitialize_torch_distributed``,
    util/distributed.py:247-259)."""
    global _info
    if _info.initialized and _info.backend not in ("single",):
        try:
            jax.distributed.shutdown()
        except Exception:
            pass
    _info = _WorkerInfo()


# ---------------------------------------------------------------------------
# control-plane collectives: KV-store object exchange + monitored barrier
# (reference util/distributed.py:121-139, pipeline.py:191-196)
# ---------------------------------------------------------------------------

def _client():
    """The jax.distributed coordination client, or None single-process."""
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client
    except Exception:
        return None


_seq = {"barrier": 0, "obj": 0}

#: Barrier ids whose arrival keys are safe to garbage-collect (the barrier
#: completed on this rank). Swept by the ROOT rank at the NEXT successful
#: barrier — see the retention note inside ``barrier()``.
_gc_barrier_ids: list = []

#: Snapshot of this rank's most recent barrier: tag, status
#: ("waiting"/"completed"/"timeout"), entry wall-clock, and — after a
#: timeout — the straggler ranks that never arrived. The telemetry flight
#: recorder (telemetry/watchdog.py) embeds this in its forensics dump so a
#: hang post-mortem names the rank everyone else was waiting on.
_barrier_state: dict = {}


def barrier_state() -> dict:
    """Copy of this rank's most recent barrier record (see ``_barrier_state``);
    empty before the first barrier."""
    return dict(_barrier_state)


class BarrierTimeout(RuntimeError):
    """A barrier timed out; ``stragglers`` lists the ranks that never arrived
    (parity with the reference's ``monitored_barrier(wait_all_ranks=True)``,
    pipeline.py:191-196, which names late ranks)."""

    def __init__(self, tag: str, timeout: float, stragglers: list[int]):
        self.tag = tag
        self.timeout = timeout
        self.stragglers = stragglers
        super().__init__(
            f"barrier '{tag}' timed out after {timeout:.0f}s; "
            f"straggler ranks (never arrived): {stragglers or 'unknown'}"
        )


def _find_stragglers(client, barrier_id: str, probe_timeout_ms: int = 200) -> list[int]:
    """Ranks whose arrival key for ``barrier_id`` is absent — probed
    concurrently with short blocking gets."""
    from concurrent.futures import ThreadPoolExecutor

    def probe(src: int) -> int | None:
        try:
            client.blocking_key_value_get(f"{barrier_id}/arrived/{src}", probe_timeout_ms)
            return None
        except Exception:
            return src

    with ThreadPoolExecutor(max_workers=min(world_size(), 32)) as ex:
        return [r for r in ex.map(probe, range(world_size())) if r is not None]


def barrier(tag: str = "", timeout: float = _DEFAULT_TIMEOUT) -> None:
    """All-process barrier with real timeout semantics that NAMES stragglers.

    The reference uses gloo ``monitored_barrier(wait_all_ranks=True)``
    (pipeline.py:191-196), whose timeout error lists the late ranks. Here
    every process drops a per-rank arrival key into the coordination-service
    KV store before waiting; on timeout the error reports exactly which ranks
    never arrived (``BarrierTimeout.stragglers``). Control-plane only: no
    device traffic.
    """
    if world_size() <= 1:
        return
    from ..telemetry import journal as _journal  # stdlib-only; no import cycle

    client = _client()
    _seq["barrier"] += 1
    barrier_id = f"dmlcloud_tpu:{tag}:{_seq['barrier']}"
    _barrier_state.clear()
    _barrier_state.update(
        {
            "tag": tag,
            "id": barrier_id,
            "rank": rank(),
            "status": "waiting",
            "entered_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "timeout_s": timeout,
        }
    )
    _t0 = _journal.now()
    if client is not None:
        # Arrival-key retention: keys are NOT deleted when their own barrier
        # completes — a rank whose timer expired in the same instant the
        # barrier completed could then misreport arrived ranks as
        # stragglers. Instead the root sweeps them ONE completed barrier
        # later (below): by the time a subsequent barrier succeeds, every
        # rank has provably left the earlier one, so its keys can no longer
        # feed any straggler probe. Bounds the coordinator's KV-store RAM to
        # O(world) keys instead of O(world x barriers) on month-long jobs.
        client.key_value_set(f"{barrier_id}/arrived/{rank()}", "1")
        try:
            client.wait_at_barrier(barrier_id, timeout_in_ms=int(timeout * 1000))
        except Exception as e:
            msg = str(e).lower()
            if "deadline" in msg or "timeout" in msg or "timed out" in msg:
                stragglers = _find_stragglers(client, barrier_id)
                # feed the flight recorder BEFORE raising: the forensics dump
                # this timeout usually precipitates must name the late ranks
                _barrier_state.update({"status": "timeout", "stragglers": stragglers})
                _journal.emit("barrier", _t0, label=tag, status="timeout", stragglers=stragglers)
                raise BarrierTimeout(tag, timeout, stragglers) from e
            _barrier_state["status"] = "error"
            raise  # not a timeout (e.g. coordinator connection lost) — do not misdiagnose
        _barrier_state["status"] = "completed"
        _journal.emit("barrier", _t0, label=tag, status="completed")
        if is_root():
            for done_id in _gc_barrier_ids:
                for src in range(world_size()):
                    try:
                        client.key_value_delete(f"{done_id}/arrived/{src}")
                    except Exception:  # best effort — a missing delete is only RAM
                        pass
        _gc_barrier_ids.clear()
        _gc_barrier_ids.append(barrier_id)
    else:  # pragma: no cover - multiprocess without coordination service
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(barrier_id)
        _barrier_state["status"] = "completed"
        _journal.emit("barrier", _t0, label=tag, status="completed")


def _kv_key(name: str, seq: int, src: int) -> str:
    return f"dmlcloud_tpu/obj/{name}/{seq}/{src}"


class CollectiveMismatchError(RuntimeError):
    """Two processes paired up collectives issued from DIFFERENT call sites.

    The object collectives match messages by a per-process sequence counter,
    which assumes every process issues the identical sequence of collective
    calls. A rank-conditional extra (or skipped) call would silently pair
    call N on one rank with a different call N on another and deliver the
    wrong object; the call-site tag carried inside every payload turns that
    into this loud error whenever the misaligned pair spans two different
    call sites. (A misalignment that realigns the SAME line with itself —
    e.g. one rank running an extra loop iteration of one collective — pairs
    identical tags and is not detectable from the tag alone.)"""

    def __init__(self, kind: str, seq: int, local_tag: str, remote_tag: str, src: int):
        self.local_tag, self.remote_tag = local_tag, remote_tag
        super().__init__(
            f"control-plane {kind} #{seq}: this process called from {local_tag} but "
            f"rank {src} published from {remote_tag} — the ranks' collective call "
            "sequences have diverged (a rank-conditional collective call?). If the "
            "differing call sites are intentional, pass the same explicit tag= on "
            "both sides."
        )


def _call_site_tag() -> str:
    """``dir/file.py:lineno`` of the first frame outside this module — the
    user call site, fingerprinting WHICH collective call this is. The last
    TWO path components are kept: a bare basename collides across packages
    (every project has a ``train.py``/``utils.py``), which would pair two
    genuinely different call sites as "matching" and let a diverged
    collective sequence deliver the wrong object undiagnosed."""
    import sys

    f = sys._getframe(1)
    while f is not None and f.f_code.co_filename == __file__:
        f = f.f_back
    if f is None:  # pragma: no cover - interpreter entry
        return "?"
    parts = f.f_code.co_filename.replace(os.sep, "/").rsplit("/", 2)
    return f"{'/'.join(parts[-2:])}:{f.f_lineno}"


def _put_obj(key: str, obj: Any, tag: str) -> None:
    payload = base64.b64encode(pickle.dumps((tag, obj))).decode("ascii")
    _client().key_value_set(key, payload)


def _get_obj(key: str, timeout: float, *, expect_tag: str, kind: str, seq: int, src: int) -> Any:
    payload = _client().blocking_key_value_get(key, int(timeout * 1000))
    remote_tag, obj = pickle.loads(base64.b64decode(payload))
    if remote_tag != expect_tag:
        raise CollectiveMismatchError(kind, seq, expect_tag, remote_tag, src)
    return obj


def broadcast_object(
    obj: Any = None, root: int = 0, timeout: float = _DEFAULT_TIMEOUT, tag: str | None = None
) -> Any:
    """Broadcast a picklable object from ``root`` to all processes
    (reference ``broadcast_object``, util/distributed.py:136-139). Rides the
    coordination-service KV store — small payloads, no device memory.

    Every payload carries a call-site tag (default: the caller's file:line)
    that receivers verify, so rank-divergent call sequences fail with
    :class:`CollectiveMismatchError` instead of silently delivering the wrong
    object. Pass an explicit shared ``tag`` when matching calls legitimately
    come from different lines (e.g. an if/else on ``is_root()``)."""
    if world_size() <= 1:
        return obj
    tag = tag or _call_site_tag()
    _seq["obj"] += 1
    seq = _seq["obj"]
    key = _kv_key("bcast", seq, root)
    if rank() == root:
        _put_obj(key, obj, tag)
        return obj
    return _get_obj(key, timeout, expect_tag=tag, kind="broadcast_object", seq=seq, src=root)


def _get_objs(name: str, seq: int, timeout: float, expect_tag: str) -> list[Any]:
    """Fetch every rank's KV entry CONCURRENTLY — ``blocking_key_value_get``
    releases the GIL during its gRPC wait, so a thread pool turns O(world)
    serial round trips into ~one."""
    from concurrent.futures import ThreadPoolExecutor

    n = world_size()

    def fetch(src: int) -> Any:
        return _get_obj(
            _kv_key(name, seq, src), timeout, expect_tag=expect_tag, kind=name, seq=seq, src=src
        )

    with ThreadPoolExecutor(max_workers=min(n, 32)) as ex:
        return list(ex.map(fetch, range(n)))


def all_gather_object(
    obj: Any, timeout: float = _DEFAULT_TIMEOUT, tag: str | None = None
) -> list[Any]:
    """Gather one picklable object from every process, returned to all ranks
    ordered by rank (reference ``all_gather_object``, util/distributed.py:121-128).
    Call-site-tag verified — see :func:`broadcast_object`."""
    if world_size() <= 1:
        return [obj]
    tag = tag or _call_site_tag()
    _seq["obj"] += 1
    seq = _seq["obj"]
    _put_obj(_kv_key("agather", seq, rank()), obj, tag)
    return _get_objs("agather", seq, timeout, tag)


def gather_object(
    obj: Any, root: int = 0, timeout: float = _DEFAULT_TIMEOUT, tag: str | None = None
) -> list[Any] | None:
    """Gather objects to ``root`` only; other ranks get None (reference
    ``gather_object``, util/distributed.py:131-133).
    Call-site-tag verified — see :func:`broadcast_object`."""
    if world_size() <= 1:
        return [obj]
    tag = tag or _call_site_tag()
    _seq["obj"] += 1
    seq = _seq["obj"]
    _put_obj(_kv_key("gather", seq, rank()), obj, tag)
    barrier("gather_object", timeout)
    if rank() != root:
        return None
    return _get_objs("gather", seq, timeout, tag)


# ---------------------------------------------------------------------------
# preemption guard (elastic resume; doc/elasticity.md)
# ---------------------------------------------------------------------------

class PreemptionGuard:
    """Signal-driven drain flag for preemption-tolerant training.

    The scheduler's eviction warning (Cloud TPU: SIGTERM; Slurm:
    ``--signal=USR1@60`` -> SIGUSR1; an operator's Ctrl-C: SIGINT) lands on
    SOME rank as an async signal. The guard turns that into a clean,
    coordinated drain: the handler only flips :attr:`triggered` (never logs
    or raises — the signal may interrupt a buffered stream), and the step
    loop polls :meth:`coordinated` at save boundaries so every rank agrees
    to stop at the SAME step — a one-sided exit would strand the survivors
    in the next collective.

    ``install()`` resolves every signal name BEFORE touching any handler (a
    typo'd name must not leave a half-installed set) and remembers the
    original dispositions for :meth:`uninstall`. ``armed`` is separate from
    installation so tests (and driver code that learns about preemption out
    of band) can flip :attr:`triggered` directly.
    """

    #: default signal set: scheduler eviction + operator interrupt, plus the
    #: Slurm warning signal when running inside a Slurm step
    DEFAULT_SIGNALS = ("SIGTERM", "SIGINT")

    def __init__(self, signals: tuple[str, ...] | None = None):
        if signals is None:
            signals = self.DEFAULT_SIGNALS
            if _slurm.slurm_available():
                signals = signals + ("SIGUSR1",)
        self.signals = tuple(signals)
        #: set (async) by the signal handler; cleared by install()
        self.triggered = False
        #: the signal name that tripped the guard, for the requeue verdict
        self.signal_name: str | None = None
        #: monotonic (perf_counter) instant the guard tripped — drain
        #: budgets (e.g. the serve engine's) are measured from here
        self.triggered_at: float | None = None
        #: whether coordinated() participates in the cross-rank gather
        self.armed = False
        self._prev: dict = {}

    def install(self) -> "PreemptionGuard":
        import signal as _signal

        sigs = [getattr(_signal, name) for name in self.signals]
        for sig in sigs:
            prev = _signal.signal(sig, self._handler)
            # re-install on the same signal keeps the ORIGINAL disposition
            self._prev.setdefault(sig, prev)
        self.triggered = False
        self.signal_name = None
        self.triggered_at = None
        self.armed = True
        return self

    def _handler(self, signum, frame):
        # flag only — the normal control path reports the drain
        import time as _time

        self.triggered = True
        self.triggered_at = _time.perf_counter()
        try:
            import signal as _signal

            self.signal_name = _signal.Signals(signum).name
        except Exception:  # pragma: no cover - exotic signum
            self.signal_name = str(signum)

    def uninstall(self) -> None:
        """Restore the original process-wide dispositions (a stale handler
        would make post-run SIGTERM a silent no-op)."""
        if self._prev:
            import signal as _signal

            for sig, prev in self._prev.items():
                _signal.signal(sig, prev)
            self._prev = {}
        self.armed = False

    def coordinated(self) -> bool:
        """Whether ANY rank caught a preemption signal — ranks must agree on
        stopping or the survivors deadlock in the next collective."""
        if not self.armed:
            return False
        if world_size() <= 1:
            return self.triggered
        return any(all_gather_object(self.triggered, tag="preemption-drain"))


def all_gather_array(x) -> np.ndarray:
    """Gather one same-shape numeric array from every process as
    ``[world, *x.shape]`` via ONE XLA collective over ICI/DCN — the fast path
    for the fused epoch-end metric exchange (metrics.py), replacing the
    per-object KV-store hops entirely. All processes must call this with the
    same shape/dtype (SPMD); a mismatch fails loudly in the collective."""
    if world_size() <= 1:
        return np.asarray(x)[None]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(np.asarray(x), tiled=False))
