"""Worker-side elastic runtime: heartbeats and the collective watchdog.

The supervisor (:mod:`.supervisor`) can only act on what it can
observe from outside the worker process. This module is the worker's
half of that contract:

- **Heartbeats** — a daemon thread writes a small JSON beat file every
  ``PYLOPS_MPI_TPU_HEARTBEAT`` seconds (atomically: temp + replace, so
  the supervisor never reads a torn beat). The thread is independent
  of the main thread, so a worker stuck inside a fused epoch or a long
  compile still beats; the beat STOPS only when the process is truly
  wedged (SIGSTOP, runaway GC, kernel-level stall) or dead — exactly
  the states the supervisor classifies as ``stale_heartbeat``.
- **The collective watchdog** — blocking host-side phases that wait on
  *peers* (``jax.distributed`` bring-up, multi-host checkpoint
  save/load) hang forever when one peer is gone; a heartbeat cannot
  catch this, because the *stuck* worker's beat thread keeps running.
  :func:`watched_call` runs such a phase in a worker thread with a
  deadline from the central :data:`~pylops_mpi_tpu.diagnostics.\
profiler.STAGE_BUDGETS` table and raises a classified :class:`WatchdogTimeout` instead of blocking
  — the worker exits nonzero, the supervisor reaps it and relaunches
  the job on the surviving host set.

Gating: the watchdog defaults to ``auto`` — armed only when the
process is SUPERVISED (``PYLOPS_MPI_TPU_HEARTBEAT_FILE`` is set by the
supervisor), so plain library use is bit-for-bit unchanged (no extra
threads, no trace events; the off-mode pins in
``tests/test_supervisor.py`` hold this). ``PYLOPS_MPI_TPU_WATCHDOG=on``
arms it unconditionally; ``off`` disarms even under supervision.

The env contract (set by :func:`.supervisor.launch_job`, read by
:func:`worker_config` / :func:`elastic_initialize`):

==================================  ====================================
``PYLOPS_MPI_TPU_COORDINATOR``      ``host:port`` of the jax.distributed
                                    coordinator for THIS attempt
``PYLOPS_MPI_TPU_NUM_PROCESSES``    world size of this attempt (shrinks
                                    after a failure)
``PYLOPS_MPI_TPU_PROCESS_ID``       this worker's rank in the attempt
``PYLOPS_MPI_TPU_ATTEMPT``          0-based relaunch counter
``PYLOPS_MPI_TPU_HEARTBEAT_FILE``   where to write beats
``PYLOPS_MPI_TPU_HEARTBEAT``        beat interval, seconds
==================================  ====================================
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from collections import namedtuple
from typing import Any, Callable, Dict, Optional

from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace
from ..diagnostics.profiler import STAGE_BUDGETS

__all__ = ["heartbeat_interval", "heartbeat_file", "HeartbeatWriter",
           "start_heartbeat", "stop_heartbeat", "maybe_start_heartbeat",
           "read_heartbeat", "WatchdogTimeout", "watchdog_mode",
           "watchdog_enabled", "watchdog_timeout", "watched_call",
           "WorkerConfig", "worker_config", "elastic_initialize",
           "request_drain", "drain_requested", "reset_drain",
           "install_sigterm_drain",
           "ElasticReconfig", "inplace_mode", "inplace_armed",
           "quorum_fraction", "reconfig_file", "pending_reconfig",
           "apply_reconfig", "reform_mesh", "bank_carry", "banked_carry",
           "clear_carry", "restore_carry"]


# ------------------------------------------------------------ heartbeats
def heartbeat_interval() -> float:
    """``PYLOPS_MPI_TPU_HEARTBEAT`` beat interval in seconds (default
    1.0; floored at 0.05 so a typo cannot busy-spin the writer)."""
    try:
        v = float(os.environ.get("PYLOPS_MPI_TPU_HEARTBEAT", "1.0"))
    except ValueError:
        v = 1.0
    return max(0.05, v)


def heartbeat_file() -> Optional[str]:
    """``PYLOPS_MPI_TPU_HEARTBEAT_FILE`` — the beat path the supervisor
    assigned this worker, or ``None`` when unsupervised."""
    return os.environ.get("PYLOPS_MPI_TPU_HEARTBEAT_FILE") or None


class HeartbeatWriter(threading.Thread):
    """Daemon thread writing ``{"pid", "seq", "wall", "mono"}`` —
    plus ``"metrics"`` (the live registry snapshot,
    ``diagnostics/metrics.py``) when ``PYLOPS_MPI_TPU_METRICS=on`` —
    to ``path`` every ``interval`` seconds, atomically (pid-suffixed
    temp + ``os.replace``), so the supervisor's reader can never
    observe a torn beat. ``stop()`` is idempotent and joins the
    thread."""

    def __init__(self, path: str, interval: float):
        super().__init__(name="pylops-heartbeat", daemon=True)
        self.path = os.path.abspath(path)
        self.interval = float(interval)
        self.seq = 0
        # NOT named _stop: Thread.join() calls a private self._stop()
        self._halt = threading.Event()

    def beat(self) -> None:
        self.seq += 1
        doc = {"pid": os.getpid(), "seq": self.seq,
               "wall": time.time(), "mono": time.monotonic()}
        # live per-worker PROGRESS, not just liveness (ISSUE 10): the
        # supervisor's read_heartbeat sees the current metrics registry
        # in every beat. One env lookup when metrics are off.
        if _metrics.metrics_enabled():
            try:
                doc["metrics"] = _metrics.snapshot()
            except Exception:
                pass  # a metrics bug must not kill the beat
        payload = json.dumps(doc)
        tmp = self.path + f".tmp{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                f.write(payload)
            os.replace(tmp, self.path)
        except OSError:
            pass  # a full disk must not kill the worker via its beat

    def run(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        self.beat()  # first beat immediately: bring-up counts as alive
        while not self._halt.wait(self.interval):
            self.beat()

    def stop(self) -> None:
        self._halt.set()
        if self.is_alive():
            self.join(timeout=5.0)


_HB_LOCK = threading.Lock()
_WRITER: Optional[HeartbeatWriter] = None


def start_heartbeat(path: Optional[str] = None,
                    interval: Optional[float] = None
                    ) -> Optional[HeartbeatWriter]:
    """Start (or return the already-running) heartbeat writer. With no
    ``path`` argument the env contract decides; returns ``None`` when
    no path is configured — the unsupervised no-op."""
    global _WRITER
    path = path or heartbeat_file()
    if path is None:
        return None
    with _HB_LOCK:
        if _WRITER is not None and _WRITER.is_alive():
            return _WRITER
        _WRITER = HeartbeatWriter(
            path, heartbeat_interval() if interval is None else interval)
        _WRITER.start()
        return _WRITER


def maybe_start_heartbeat() -> Optional[HeartbeatWriter]:
    """Env-driven auto-start used by long-running entry points (the
    segmented solvers): one dict lookup when unsupervised, the running
    writer when supervised. Safe to call from anywhere, any number of
    times."""
    if heartbeat_file() is None:
        return None
    return start_heartbeat()


def stop_heartbeat() -> None:
    global _WRITER
    with _HB_LOCK:
        if _WRITER is not None:
            _WRITER.stop()
            _WRITER = None


def read_heartbeat(path: str) -> Optional[Dict[str, Any]]:
    """Supervisor-side beat reader: the parsed beat dict, or ``None``
    when the file is missing or (transiently) unparseable."""
    try:
        with open(path) as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


# ------------------------------------------------------------- watchdog
class WatchdogTimeout(RuntimeError):
    """A watched host-side phase blew its deadline — a hung peer, not
    a slow computation. Carries ``stage`` and ``timeout_s`` so the
    supervisor's failure record (and the trace event) name the phase
    that wedged."""

    def __init__(self, stage: str, timeout_s: float):
        self.stage = stage
        self.timeout_s = float(timeout_s)
        super().__init__(
            f"watchdog: stage {stage!r} still blocked after "
            f"{timeout_s:.0f}s — a peer is likely hung or gone; "
            "exiting so the supervisor can relaunch on the surviving "
            "hosts (docs/robustness.md#collective-watchdog)")


_WD_MODES = ("auto", "on", "off")
_warned_wd = False


def watchdog_mode() -> str:
    """``PYLOPS_MPI_TPU_WATCHDOG`` resolved to ``auto``/``on``/``off``
    (default ``auto``; unknown values warn once and fall back to
    ``auto`` — same rule as the overlap/trace knobs)."""
    global _warned_wd
    m = os.environ.get("PYLOPS_MPI_TPU_WATCHDOG", "auto").strip().lower()
    if m in ("", "none", "default"):
        m = "auto"
    if m not in _WD_MODES:
        if not _warned_wd:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_WATCHDOG={m!r} is not one of "
                f"{_WD_MODES}; using 'auto'", stacklevel=2)
            _warned_wd = True
        m = "auto"
    return m


def watchdog_enabled() -> bool:
    """``on`` → armed; ``off`` → disarmed; ``auto`` (default) → armed
    only when this process is supervised (a heartbeat file is
    configured) — plain library use never grows watchdog threads."""
    m = watchdog_mode()
    if m == "on":
        return True
    if m == "off":
        return False
    return heartbeat_file() is not None


def watchdog_timeout(stage: str, default: Optional[float] = None) -> float:
    """Deadline for one watched stage: the global override
    ``PYLOPS_MPI_TPU_WATCHDOG_TIMEOUT`` when set, else the stage's
    entry in the central ``STAGE_BUDGETS`` table, else ``default``
    (300 s)."""
    raw = os.environ.get("PYLOPS_MPI_TPU_WATCHDOG_TIMEOUT")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    if stage in STAGE_BUDGETS:
        return float(STAGE_BUDGETS[stage])
    return 300.0 if default is None else float(default)


_wd_tls = threading.local()  # reentrancy: nested watched phases run direct


def watched_call(fn: Callable, *args, stage: str,
                 timeout_s: Optional[float] = None, **kwargs):
    """Run ``fn(*args, **kwargs)`` under the collective watchdog.

    Disarmed (the default, unsupervised case) this is a direct call —
    zero threads, zero trace events, bit-identical behavior. Armed, the
    call runs in a daemon worker thread with deadline
    ``timeout_s`` (default: :func:`watchdog_timeout` for ``stage``);
    if the deadline passes, a ``resilience.watchdog`` trace event is
    emitted and :class:`WatchdogTimeout` is raised in the CALLER —
    the blocked thread is left behind (Python cannot kill it), which
    is exactly right for a supervised worker: the raise unwinds to a
    nonzero exit and the supervisor reaps the whole process. Nested
    watched phases (checkpoint-inside-harvest) run direct under the
    outer deadline instead of stacking threads."""
    if not watchdog_enabled() or getattr(_wd_tls, "active", False):
        return fn(*args, **kwargs)
    deadline = watchdog_timeout(stage) if timeout_s is None \
        else float(timeout_s)
    out: "queue.Queue" = queue.Queue(maxsize=1)

    def runner():
        _wd_tls.active = True
        try:
            out.put((True, fn(*args, **kwargs)))
        except BaseException as e:  # noqa: BLE001 — relayed to caller
            out.put((False, e))

    t = threading.Thread(target=runner, daemon=True,
                         name=f"pylops-watchdog-{stage}")
    with _trace.span("resilience.watchdog", cat="resilience",
                     stage=stage, timeout_s=deadline):
        t.start()
        try:
            ok, payload = out.get(timeout=deadline)
        except queue.Empty:
            _trace.event("resilience.watchdog_timeout", cat="resilience",
                         stage=stage, timeout_s=deadline)
            raise WatchdogTimeout(stage, deadline) from None
    if ok:
        return payload
    raise payload


# -------------------------------------------------------- drain signal
# SIGTERM semantics for serve-forever workers (serving/service.py):
# the deployment's stop is a DRAIN, not a kill — finish in-flight
# batches, refuse new claims, then exit 0. A signal handler can only
# run on the main thread; the serving loops poll this event instead.
_DRAIN = threading.Event()
_prev_sigterm: Any = None


def request_drain() -> None:
    """Ask this process's serving loops to drain and exit (idempotent;
    also callable directly, e.g. from tests or an admin endpoint)."""
    if not _DRAIN.is_set():
        _DRAIN.set()
        _trace.event("resilience.drain_requested", cat="resilience",
                     pid=os.getpid())
        _metrics.inc("serve.drain_requests")


def drain_requested() -> bool:
    """Whether a drain has been requested for this process."""
    return _DRAIN.is_set()


def reset_drain() -> None:
    """Clear the drain flag (test isolation; a served process never
    un-drains)."""
    _DRAIN.clear()


def install_sigterm_drain() -> bool:
    """Route SIGTERM to :func:`request_drain` (chaining any previous
    handler). Returns False — leaving signal disposition untouched —
    when not on the main thread, where Python forbids ``signal.signal``.
    Idempotent: a second install keeps the first chain."""
    import signal as _signal
    global _prev_sigterm
    if threading.current_thread() is not threading.main_thread():
        return False
    current = _signal.getsignal(_signal.SIGTERM)
    if getattr(current, "_pylops_drain", False):
        return True  # already installed

    def _handler(signum, frame):
        request_drain()
        if callable(current) and current not in (
                _signal.SIG_IGN, _signal.SIG_DFL):
            current(signum, frame)

    _handler._pylops_drain = True
    _prev_sigterm = current
    _signal.signal(_signal.SIGTERM, _handler)
    return True


# ----------------------------------------------------- worker bring-up
WorkerConfig = namedtuple(
    "WorkerConfig", ["coordinator", "num_processes", "process_id",
                     "attempt", "heartbeat_path", "heartbeat_s"])
WorkerConfig.__doc__ = (
    "The supervisor-assigned identity of this worker process for the "
    "CURRENT attempt: coordinator address, (possibly shrunk) world "
    "size, rank, 0-based relaunch counter, and the heartbeat "
    "assignment. Unsupervised processes get "
    "(None, None, None, 0, None, interval).")


def _env_int(name: str) -> Optional[int]:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def worker_config() -> WorkerConfig:
    """Read the supervisor env contract (module docstring)."""
    return WorkerConfig(
        coordinator=os.environ.get("PYLOPS_MPI_TPU_COORDINATOR") or None,
        num_processes=_env_int("PYLOPS_MPI_TPU_NUM_PROCESSES"),
        process_id=_env_int("PYLOPS_MPI_TPU_PROCESS_ID"),
        attempt=_env_int("PYLOPS_MPI_TPU_ATTEMPT") or 0,
        heartbeat_path=heartbeat_file(),
        heartbeat_s=heartbeat_interval())


# ----------------------------------------- in-place reconfiguration
# Round 13. The classic recovery ladder (supervisor kills the whole
# attempt, relaunches shrunk, workers resume FROM CHECKPOINT) pays a
# full checkpoint write+read on every failure. The in-place path keeps
# the survivors alive: the supervisor classifies the dead worker,
# writes each survivor a reconfig file naming the shrunk world, and the
# survivor — which has been banking the fused-solver carry at every
# epoch boundary (host-replicated via collectives, bounded-scratch) —
# re-forms its mesh and replans the carry onto it with
# ``parallel/reshard.place_replica``. No checkpoint I/O on the
# recovery path; the checkpoint ladder stays as the fallback whenever
# the quorum fails, the planner refuses, or the survivor itself dies
# mid-reshard (the ``faults.maybe_kill_reshard`` chaos seam).
INPLACE_ENV = "PYLOPS_MPI_TPU_INPLACE"
QUORUM_ENV = "PYLOPS_MPI_TPU_QUORUM"
RECONFIG_ENV = "PYLOPS_MPI_TPU_RECONFIG_FILE"

_IP_MODES = ("auto", "on", "off")
_warned_ip = False


class ElasticReconfig(RuntimeError):
    """The supervisor reassigned this worker to a shrunk world while a
    solve was running. Raised at the next epoch boundary; carries the
    parsed reconfig ``config`` dict so the catcher can
    :func:`apply_reconfig`, :func:`reform_mesh`, and resume from the
    banked carry (:func:`restore_carry`) — or fall back to the
    checkpoint when any of those refuse."""

    def __init__(self, config: Dict[str, Any]):
        self.config = dict(config)
        super().__init__(
            f"elastic reconfig: attempt {config.get('attempt')} world "
            f"{config.get('num_processes')} rank "
            f"{config.get('process_id')} (in-place shrink; resume from "
            "the banked carry or fall back to the checkpoint)")


def inplace_mode() -> str:
    """``PYLOPS_MPI_TPU_INPLACE`` resolved to ``auto``/``on``/``off``
    (default ``auto``; unknown values warn once and fall back —
    the watchdog knob's rule)."""
    global _warned_ip
    m = os.environ.get(INPLACE_ENV, "auto").strip().lower()
    if m in ("", "none", "default"):
        m = "auto"
    if m not in _IP_MODES:
        if not _warned_ip:
            import warnings
            warnings.warn(f"{INPLACE_ENV}={m!r} is not one of "
                          f"{_IP_MODES}; using 'auto'", stacklevel=2)
            _warned_ip = True
        m = "auto"
    return m


def reconfig_file() -> Optional[str]:
    """The reconfig path the supervisor assigned this worker (set only
    when the job was launched with ``inplace=True``), or ``None``."""
    return os.environ.get(RECONFIG_ENV) or None


def inplace_armed() -> bool:
    """``on`` → armed; ``off`` → disarmed; ``auto`` (default) → armed
    only when the supervisor assigned a reconfig file — plain library
    use never banks carries or polls for reconfigs."""
    m = inplace_mode()
    if m == "on":
        return True
    if m == "off":
        return False
    return reconfig_file() is not None


def quorum_fraction() -> float:
    """``PYLOPS_MPI_TPU_QUORUM``: the fraction of the launch world
    that must survive a failure for the in-place path to engage
    (default 0.5; clamped to (0, 1]). Below quorum the supervisor
    takes the checkpoint-relaunch ladder — too much state died to
    trust a live patch-up."""
    try:
        v = float(os.environ.get(QUORUM_ENV, "0.5"))
    except ValueError:
        v = 0.5
    return min(1.0, max(1e-9, v))


def pending_reconfig() -> Optional[Dict[str, Any]]:
    """The supervisor's reconfig assignment for this worker, parsed,
    when it names an attempt NEWER than the one this process is
    running — else ``None``. (Applying a reconfig bumps
    ``PYLOPS_MPI_TPU_ATTEMPT``, which is what marks it consumed.)"""
    path = reconfig_file()
    if path is None or not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            doc = json.loads(f.read())
    except (OSError, ValueError):
        return None  # torn write: the next poll sees the full file
    if not isinstance(doc, dict) or "attempt" not in doc:
        return None
    cur = _env_int("PYLOPS_MPI_TPU_ATTEMPT") or 0
    if int(doc["attempt"]) <= cur:
        return None
    return doc


def apply_reconfig(config: Dict[str, Any]) -> WorkerConfig:
    """Adopt a reconfig assignment: rewrite the worker env contract
    (world size, rank, attempt, coordinator) so
    :func:`worker_config` — and :func:`pending_reconfig`'s consumed
    check — reflect the shrunk world. Returns the new config."""
    os.environ["PYLOPS_MPI_TPU_NUM_PROCESSES"] = \
        str(int(config["num_processes"]))
    os.environ["PYLOPS_MPI_TPU_PROCESS_ID"] = \
        str(int(config["process_id"]))
    os.environ["PYLOPS_MPI_TPU_ATTEMPT"] = str(int(config["attempt"]))
    if config.get("coordinator"):
        os.environ["PYLOPS_MPI_TPU_COORDINATOR"] = \
            str(config["coordinator"])
    _trace.event("resilience.reconfig_applied", cat="resilience",
                 attempt=int(config["attempt"]),
                 world=int(config["num_processes"]),
                 rank=int(config["process_id"]))
    return worker_config()


def reform_mesh(cfg: WorkerConfig):
    """Re-form this survivor's mesh for the shrunk world WITHOUT a
    process restart. A one-process world gets a mesh over
    ``jax.local_devices()`` — NOT ``jax.devices()``, which still lists
    the dead peer's remote devices while the old ``jax.distributed``
    client lingers. A multi-process reform would need that client torn
    down and re-initialized, and its shutdown is a collective barrier
    that hangs when a peer is dead — so multi-survivor worlds refuse
    here and take the checkpoint-relaunch fallback (the quorum/fallback
    table, docs/robustness.md#in-place-recovery)."""
    world = cfg.num_processes or 1
    if world > 1:
        raise RuntimeError(
            "reform_mesh: re-forming a multi-process world in place "
            "needs a jax.distributed restart, whose shutdown barrier "
            "hangs while a peer is dead; fall back to the checkpoint "
            "relaunch path")
    import jax
    from jax.sharding import Mesh
    import numpy as np
    devs = jax.local_devices()
    from ..parallel.mesh import SP_AXIS
    mesh = Mesh(np.asarray(devs), (SP_AXIS,))
    _trace.event("resilience.mesh_reformed", cat="resilience",
                 world=world, n_devices=len(devs))
    return mesh


# ------------------------------------------------- survivor carry bank
# The bank holds one host-replicated snapshot of the fused-solver
# carry per tag ("cg"/"cgls"), refreshed at every epoch boundary while
# in-place recovery is armed. Vector fields are gathered to host
# through collectives (``process_allgather`` of the physical pad-to-max
# buffer, then the static unpad map) — every process holds the full
# logical value, so any survivor can replant it alone.
_BANK_LOCK = threading.Lock()
_BANK: Dict[str, Dict[str, Any]] = {}


def _host_value(arr) -> Any:
    """Host numpy copy of a (possibly multi-process-replicated) jax
    array: a non-fully-addressable input goes through the allgather
    (which returns it fully replicated), local data copies directly."""
    import numpy as np
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(arr))


def _host_global(darr) -> Any:
    """Host copy of a DistributedArray's logical global value, via an
    allgather when shards live on other processes."""
    import numpy as np
    phys = _host_value(darr._arr)
    if darr._even:
        return phys
    from ..parallel.partition import unpad_index_map
    idx = unpad_index_map(darr._axis_sizes, darr._s_phys)
    return np.take(phys, idx, axis=darr._axis)


def bank_carry(tag: str, carry: Dict[str, Any]) -> None:
    """Bank one epoch-boundary carry snapshot under ``tag``. Vector
    fields (DistributedArrays) are recorded as host-replicated values
    plus their layout (partition/axis/shard-count/mask); everything
    else as plain host scalars/arrays. Stacked vectors are not
    bankable — banking refuses (and in-place recovery falls back to
    the checkpoint) rather than guessing a layout."""
    import numpy as np
    from ..distributedarray import DistributedArray
    rec: Dict[str, Any] = {}
    for name, val in carry.items():
        if isinstance(val, DistributedArray):
            rec[name] = {"kind": "dist",
                         "partition": val.partition.name,
                         "axis": int(val.axis),
                         "n_shards": int(val.n_shards),
                         "mask": (tuple(val.mask)
                                  if val.mask is not None else None),
                         "value": _host_global(val)}
        elif hasattr(val, "distarrays"):  # StackedDistributedArray
            raise TypeError(
                f"bank_carry: field {name!r} is a stacked vector; "
                "in-place banking supports flat DistributedArray "
                "carries only — run with the checkpoint fallback")
        elif isinstance(val, (int, float, str, bool, type(None))):
            rec[name] = {"kind": "raw", "value": val}
        else:
            rec[name] = {"kind": "array", "value": _host_value(val)}
    with _BANK_LOCK:
        _BANK[tag] = {"wall": time.time(), "fields": rec}
    _trace.event("resilience.carry_banked", cat="resilience", tag=tag,
                 n_fields=len(rec))


def banked_carry(tag: str) -> Optional[Dict[str, Any]]:
    """The raw banked record for ``tag`` (or ``None``) — test/debug
    introspection; consumers use :func:`restore_carry`."""
    with _BANK_LOCK:
        return _BANK.get(tag)


def clear_carry(tag: Optional[str] = None) -> None:
    with _BANK_LOCK:
        if tag is None:
            _BANK.clear()
        else:
            _BANK.pop(tag, None)


def restore_carry(tag: str, mesh, budget=None, chunks=None
                  ) -> Dict[str, Any]:
    """Replant the banked carry onto ``mesh`` (the re-formed, shrunk
    mesh) through the bounded-memory resharding planner — each vector
    field via :func:`~pylops_mpi_tpu.parallel.reshard.place_replica`
    with a fresh balanced split for the new world. Raises ``KeyError``
    when nothing is banked and lets planner refusals
    (:class:`~pylops_mpi_tpu.parallel.reshard.ReshardError` — budget,
    mask, short axis) propagate: the caller's fallback is the
    checkpoint. NO checkpoint I/O happens here — that absence is
    trace-pinned by the chaos acceptance test."""
    from ..parallel import reshard as _reshard
    from ..parallel.partition import Partition
    import jax.numpy as jnp
    with _BANK_LOCK:
        bank = _BANK.get(tag)
    if bank is None:
        raise KeyError(f"restore_carry: no banked carry for tag {tag!r}")
    n_new = int(mesh.devices.size)
    state: Dict[str, Any] = {}
    for name, rec in bank["fields"].items():
        kind = rec["kind"]
        if kind == "dist":
            if rec["mask"] is not None and rec["n_shards"] != n_new:
                raise _reshard.ReshardError(
                    f"restore_carry: field {name!r} carries a mask and "
                    f"the world changed {rec['n_shards']} -> {n_new}; "
                    "masks are per-shard group colors — fall back to "
                    "the checkpoint path", 0)
            state[name] = _reshard.place_replica(
                rec["value"], mesh, Partition[rec["partition"]],
                rec["axis"],
                mask=(rec["mask"] if rec["n_shards"] == n_new else None),
                budget=(budget if budget is not None
                        else _reshard._UNSET),
                chunks=chunks)
        elif kind == "raw":
            state[name] = rec["value"]
        else:
            state[name] = jnp.asarray(rec["value"])
    _trace.event("resilience.inplace_recovery", cat="resilience",
                 tag=tag, n_fields=len(state), world_devices=n_new)
    _metrics.inc("resilience.inplace_recoveries")
    return state


def elastic_initialize() -> WorkerConfig:
    """One-call worker bring-up for supervised jobs: start the
    heartbeat, then — when this attempt's world has more than one
    process — join the ``jax.distributed`` job named by the env
    contract (under the bounded retry AND the collective watchdog via
    :func:`~pylops_mpi_tpu.parallel.mesh.initialize_multihost`).
    Single-process attempts (the shrunk mesh after every peer failed)
    skip the distributed runtime entirely and run on local devices.
    Returns the :class:`WorkerConfig` so the worker can build its
    (possibly shrunk) mesh from ``num_processes``."""
    cfg = worker_config()
    maybe_start_heartbeat()
    if cfg.num_processes is not None and cfg.num_processes > 1:
        from ..parallel.mesh import initialize_multihost
        initialize_multihost(coordinator_address=cfg.coordinator,
                             num_processes=cfg.num_processes,
                             process_id=cfg.process_id)
    _trace.event("resilience.elastic_init", cat="resilience",
                 attempt=cfg.attempt, world=cfg.num_processes or 1,
                 rank=cfg.process_id or 0)
    return cfg
