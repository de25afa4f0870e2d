"""Feature flags / environment configuration.

Rebuild of ``pylops_mpi/utils/deps.py:1-66``. The reference's flags pick
between MPI, CUDA-aware MPI and NCCL backends at import time
(``NCCL_PYLOPS_MPI``, ``PYLOPS_MPI_CUDA_AWARE``). The TPU build has one
backend — XLA collectives — so the seam carries different switches:

- ``PYLOPS_MPI_TPU_PLATFORM``: request a ``jax_platforms`` value (e.g.
  ``cpu`` for the 8-virtual-device simulation) before first backend
  use. A request, never a fallback: when the platform asked for cannot
  initialise, JAX raises.
- ``PYLOPS_MPI_TPU_X64``: enable float64 (defaults to JAX's setting;
  TPUs prefer f32/bf16).
- ``BENCH_PYLOPS_MPI`` / ``BENCH_PYLOPS_MPI_TPU``: benchmark kill-switch
  (ref ``utils/benchmark.py:25``; both names honoured).
- ``TEST_CUPY_PYLOPS`` has no analog (no CuPy engine); kept as a no-op
  recognised name so reference test-harness scripts don't break.
- ``PYLOPS_MPI_TPU_MATMUL_PRECISION``: default ``highest`` — on TPU the
  stock matmul precision decomposes f32 operands into bf16 MXU passes
  (~1e-3 relative error, measured on hardware by the round-3
  selfcheck's SUMMA check), which breaks numerics parity with the
  reference's true-f32 GEMMs. Pinning ``jax_default_matmul_precision``
  makes ``float32`` operators mean float32; the fast path stays
  available explicitly through ``compute_dtype=bfloat16`` (bf16 inputs
  are unaffected by the precision flag). Set to ``default`` to restore
  JAX's backend default.
- ``PYLOPS_MPI_TPU_OVERLAP``: ``auto`` (default) | ``on`` | ``off`` —
  the pipelined-collectives seam (round 8). ``on`` switches the
  comm-heavy operator families to overlapped schedules: ring SUMMA
  (double-buffered ``ppermute`` + per-step GEMM instead of bulk
  gather/psum), chunked pencil transposes (K tiled ``all_to_all``\\ s
  interleaved with the per-chunk local transforms), and
  interior/boundary-split halo stencils (ghost ``ppermute``\\ s in
  flight while the interior computes). ``off`` keeps the bulk
  schedules bit-identical to pre-round-8 results; ``auto`` enables the
  overlap only on real TPU backends, where it hides ICI transfer
  behind MXU compute — on the CPU simulation the chunked schedules
  only add dispatches. Per-operator ``overlap=`` kwargs override the
  env. (SUMMA reads its ``auto`` more finely: its adjoint chooses per
  product — ``ops/matrixmult.py::_ring_pays`` — and its stationary-A
  forward has no ring.)
- ``PYLOPS_MPI_TPU_COMM_CHUNKS``: default chunk count (4) for the
  streamed pencil transposes when the overlap is enabled; per-operator
  ``comm_chunks=`` wins. Chunk counts that don't fit the axis fall
  back (logged) instead of erroring.
- ``PYLOPS_MPI_TPU_HIERARCHICAL``: ``auto`` (default) | ``on`` |
  ``off`` — the topology-aware collectives seam (round 11). ``on``
  switches the comm-heavy operators to hierarchical schedules on
  hybrid (dcn × ici) meshes: two-level pencil transposes that keep the
  dense shuffle on ICI and stage one smaller exchange over DCN,
  slice-staged rings and two-level reduce-scatter/all-gather. ``off``
  keeps the flat schedules bit-identical; ``auto`` engages on real TPU
  backends or when ``PYLOPS_MPI_TPU_FABRIC`` declares a simulated
  fabric. Per-operator ``hierarchical=`` kwargs override the env.
- ``PYLOPS_MPI_TPU_FABRIC``: ``DxI`` (e.g. ``2x4``) — CPU-sim fabric
  override for :mod:`pylops_mpi_tpu.parallel.topology`: classify the
  device list as D slices of I devices each when deciding which mesh
  axes are ICI vs DCN.
- ``PYLOPS_MPI_TPU_TRACE`` / ``PYLOPS_MPI_TPU_TELEMETRY`` /
  ``PYLOPS_MPI_TPU_TRACE_FILE`` /
  ``PYLOPS_MPI_TPU_METRICS`` (``_FILE``, ``_INTERVAL``): the
  observability seams (rounds 9/10) — structured span tracing, in-loop
  solver telemetry and the fleet metrics registry. Resolved by :mod:`pylops_mpi_tpu.diagnostics` (see
  ``docs/observability.md``), not here, so the jax-free scripts can
  read them standalone.
"""

from __future__ import annotations

import os

__all__ = ["jax_enabled", "platform_override", "x64_enabled",
           "explicit_stencil_enabled", "apply_environment",
           "overlap_mode", "overlap_enabled", "comm_chunks_default",
           "batch_default",
           "overlap_env_pinned", "comm_chunks_env_pinned",
           "hierarchical_mode", "hierarchical_enabled",
           "hierarchical_env_pinned",
           "KNOBS", "knob_names", "knob_table_markdown"]

jax_enabled = True  # the only engine; mirrors deps.nccl_enabled's role


# --------------------------------------------------------- knob registry
# The ONE table of every PYLOPS_MPI_TPU_* environment knob (round 10):
# (name, values, default, consumer module(s), one-line purpose).
# tests/test_tuning.py greps the package for knob reads and fails on
# any knob missing here; docs/tpu.md renders this table
# (knob_table_markdown) instead of per-PR ad-hoc lists. Add a row when
# you add a knob — or better, register a tuning space
# (pylops_mpi_tpu/tuning/space.py) instead of adding one.
KNOBS = [
    ("PYLOPS_MPI_TPU_PLATFORM", "cpu|tpu|…", "unset (what JAX finds)",
     "utils/deps.py",
     "request a JAX platform before first backend use (a request, "
     "never a fallback)"),
    ("PYLOPS_MPI_TPU_X64", "0|1", "0", "utils/deps.py",
     "enable float64 (TPUs prefer f32/bf16)"),
    ("PYLOPS_MPI_TPU_MATMUL_PRECISION", "highest|default|…", "highest",
     "utils/deps.py",
     "jax_default_matmul_precision pin (f32 means f32 on the MXU)"),
    ("PYLOPS_MPI_TPU_EXPLICIT_STENCIL", "0|1", "1",
     "utils/deps.py, ops/derivatives.py",
     "hand-scheduled shard_map stencil path vs implicit GSPMD"),
    ("PYLOPS_MPI_TPU_OVERLAP", "auto|on|off", "auto",
     "utils/deps.py (ops/matrixmult|fft|stack|derivatives|halo)",
     "pipelined-collectives seam: ring SUMMA, chunked transposes, "
     "split halo stencils (`auto`: on for a TPU, except that SUMMA's "
     "stationary-A forward and adjoint decide per product)"),
    ("PYLOPS_MPI_TPU_COMM_CHUNKS", "int>=1", "4",
     "utils/deps.py, ops/fft.py",
     "default chunk count for streamed pencil transposes"),
    ("PYLOPS_MPI_TPU_RESHARD_BUDGET", "bytes (k/m/g suffixes)",
     "unset (unbounded)", "parallel/reshard.py",
     "peak per-device scratch ceiling of the resharding planner; a "
     "move that cannot fit refuses with the minimum budget that "
     "would succeed"),
    ("PYLOPS_MPI_TPU_SPILL", "auto|on|off", "auto",
     "utils/deps.py (parallel/reshard.py, parallel/spill.py)",
     "host-RAM spill tier for the resharding planner: auto converts "
     "only would-refuse moves into double-buffered host-staged "
     "schedules, on forces host staging for every concrete move, off "
     "keeps the round-13 refusal behavior bit-identical"),
    ("PYLOPS_MPI_TPU_HIERARCHICAL", "auto|on|off", "auto",
     "utils/deps.py (parallel/topology.py, "
     "ops/matrixmult|fft|stack|halo|derivatives)",
     "topology-aware hierarchical collectives on hybrid (dcn x ici) "
     "meshes: two-level pencil transposes, slice-staged rings, "
     "per-fabric byte accounting; off keeps the flat schedules "
     "bit-identical"),
    ("PYLOPS_MPI_TPU_FABRIC", "DxI (e.g. 2x4)", "unset (detect)",
     "parallel/topology.py",
     "fabric override for CPU-sim testing: treat the device list as D "
     "slices of I devices each (id-major) when classifying mesh axes "
     "as ICI/DCN"),
    ("PYLOPS_MPI_TPU_PRECISION", "f32|bf16|c64", "f32",
     "ops/_precision.py",
     "storage/compute precision policy for operators built with "
     "compute_dtype=None"),
    ("PYLOPS_MPI_TPU_DONATE", "0|1", "1",
     "ops/_precision.py, solvers/basic.py, utils/hlo.py",
     "buffer donation of the fused solvers' model-vector argument"),
    ("PYLOPS_MPI_TPU_FUSED_CACHE", "int>=1", "32", "solvers/basic.py",
     "fused-solver executable cache capacity"),
    ("PYLOPS_MPI_TPU_FFT_MODE", "auto|xla|matmul|planar", "auto",
     "ops/dft.py",
     "local-FFT engine seam (auto = xla; planar = complex-free plane "
     "pairs)"),
    ("PYLOPS_MPI_TPU_DFT_BASE", "int", "128 on TPU / 16 on CPU",
     "ops/dft.py", "mixed-radix GEMM base of the matmul DFT engine"),
    ("PYLOPS_MPI_TPU_NATIVE", "0|1", "1", "native/__init__.py",
     "build/load the native host-pack helper library"),
    ("PYLOPS_MPI_TPU_NATIVE_THREADS", "int", "min(16, cores)",
     "native/__init__.py", "threads for native pack/IO helpers"),
    ("PYLOPS_MPI_TPU_CKPT_BACKEND", "native|orbax", "native",
     "utils/checkpoint.py", "checkpoint encode/decode backend"),
    ("PYLOPS_MPI_TPU_GUARDS", "off|on", "off",
     "resilience/status.py (solvers/basic.py, solvers/sparsity.py)",
     "in-loop breakdown/stagnation guards in the fused solvers; off "
     "traces bit-identical programs"),
    ("PYLOPS_MPI_TPU_GUARD_STALL", "int>=2", "50",
     "resilience/status.py",
     "stagnation window: iterations without a new best residual "
     "before status=stagnation"),
    ("PYLOPS_MPI_TPU_RESTARTS", "int>=0", "2",
     "resilience/driver.py",
     "max precision-escalation restarts of resilient_solve"),
    ("PYLOPS_MPI_TPU_SEGMENT", "int>=0", "0 (one segment)",
     "solvers/segmented.py",
     "default epoch length of the segmented fused solvers "
     "(checkpoint cadence)"),
    ("PYLOPS_MPI_TPU_RETRIES", "int>=0", "3",
     "resilience/retry.py (parallel/mesh.py)",
     "bounded retries for transient host-side faults (multihost "
     "init)"),
    ("PYLOPS_MPI_TPU_RETRY_BACKOFF", "seconds", "0.5",
     "resilience/retry.py",
     "initial retry backoff (doubling, capped at 30 s)"),
    ("PYLOPS_MPI_TPU_TRACE", "off|spans|full", "off",
     "diagnostics/trace.py (linearoperator, collectives, solvers)",
     "structured span tracing; full adds in-loop solver telemetry"),
    ("PYLOPS_MPI_TPU_TRACE_FILE", "path", "unset",
     "diagnostics/trace.py", "auto-dump the trace JSONL at exit"),
    ("PYLOPS_MPI_TPU_TRACE_BUFFER", "int", "65536",
     "diagnostics/trace.py", "trace ring-buffer capacity (events)"),
    ("PYLOPS_MPI_TPU_TELEMETRY", "auto|on|off", "auto",
     "diagnostics/telemetry.py",
     "in-loop solver telemetry gate under TRACE=full"),
    ("PYLOPS_MPI_TPU_TUNE", "off|on|auto", "off",
     "tuning/plan.py (ops/*)",
     "autotuner seam: on replays cached/cost-model plans, auto also "
     "measures on cache miss"),
    ("PYLOPS_MPI_TPU_TUNE_CACHE", "path", "unset (memory-only)",
     "tuning/cache.py", "persistent JSON plan cache"),
    ("PYLOPS_MPI_TPU_TUNE_BUDGET", "seconds", "STAGE_BUDGETS['tune']",
     "tuning/search.py", "wall budget for one measurement search"),
    ("PYLOPS_MPI_TPU_TUNE_TOPK", "int>=1", "4", "tuning/search.py",
     "how many seed-ranked candidates get timed"),
    ("PYLOPS_MPI_TPU_TUNE_MARGIN", "float", "0.02", "tuning/search.py",
     "fractional win required to move off the default plan"),
    ("PYLOPS_MPI_TPU_BATCH", "int>=1", "1",
     "utils/deps.py (tuning contexts)",
     "default RHS-column count K of the batched solve paths (carried "
     "into plan-cache keys)"),
    ("PYLOPS_MPI_TPU_TEST_DEVICES", "int", "8",
     "tests/conftest.py, .github/workflows/build.yml",
     "virtual-device count of the CPU-sim test mesh"),
    ("PYLOPS_MPI_TPU_RETRY_JITTER", "float in [0,1]", "0",
     "resilience/retry.py",
     "decorrelating backoff jitter fraction (supervisor sets 0.25 for "
     "workers so reconnects don't stampede)"),
    ("PYLOPS_MPI_TPU_HEARTBEAT", "seconds", "1.0",
     "resilience/elastic.py",
     "heartbeat-write interval of supervised workers"),
    ("PYLOPS_MPI_TPU_HEARTBEAT_FILE", "path", "unset (unsupervised)",
     "resilience/elastic.py (set by resilience/supervisor.py)",
     "per-worker beat file; also the auto trigger for the collective "
     "watchdog"),
    ("PYLOPS_MPI_TPU_WATCHDOG", "auto|on|off", "auto",
     "resilience/elastic.py (parallel/mesh.py, utils/checkpoint.py)",
     "collective watchdog over blocking host-side phases; auto arms "
     "only under supervision, off is bit-identical"),
    ("PYLOPS_MPI_TPU_WATCHDOG_TIMEOUT", "seconds",
     "STAGE_BUDGETS per stage", "resilience/elastic.py",
     "global override of every watched stage's deadline"),
    ("PYLOPS_MPI_TPU_COORDINATOR", "host:port", "set by supervisor",
     "resilience/elastic.py, resilience/supervisor.py",
     "jax.distributed coordinator address of the current attempt"),
    ("PYLOPS_MPI_TPU_NUM_PROCESSES", "int>=1", "set by supervisor",
     "resilience/elastic.py, resilience/supervisor.py",
     "world size of the current attempt (shrinks after failures)"),
    ("PYLOPS_MPI_TPU_PROCESS_ID", "int>=0", "set by supervisor",
     "resilience/elastic.py, resilience/supervisor.py",
     "this worker's rank within the current attempt"),
    ("PYLOPS_MPI_TPU_ATTEMPT", "int>=0", "set by supervisor",
     "resilience/elastic.py, resilience/supervisor.py",
     "0-based relaunch counter of the supervised job"),
    ("PYLOPS_MPI_TPU_INPLACE", "auto|on|off", "auto",
     "resilience/elastic.py (solvers/segmented.py)",
     "in-place (no-checkpoint) elastic recovery: survivors bank the "
     "solver carry each epoch and replan it onto the shrunk mesh on a "
     "reconfig; auto arms only when the supervisor assigned a "
     "reconfig file"),
    ("PYLOPS_MPI_TPU_QUORUM", "float in (0,1]", "0.5",
     "resilience/elastic.py, resilience/supervisor.py",
     "surviving fraction of the attempt's world required before the "
     "in-place path engages; below it the checkpoint-relaunch ladder "
     "runs"),
    ("PYLOPS_MPI_TPU_RECONFIG_FILE", "path",
     "unset (set by supervisor under inplace=True)",
     "resilience/elastic.py (resilience/supervisor.py)",
     "per-worker in-place reassignment file; its presence is the auto "
     "trigger for carry banking and reconfig polling"),
    ("PYLOPS_MPI_TPU_FAULT_KILL_RESHARD", "int>=1", "unset (off)",
     "resilience/faults.py (parallel/reshard.py)",
     "chaos seam: SIGKILL this process when the reshard-step counter "
     "reaches N — rehearses a worker dying mid-reshard so the "
     "checkpoint fallback path stays proven"),
    ("PYLOPS_MPI_TPU_FAULT_KILL_SPILL", "int>=1", "unset (off)",
     "resilience/faults.py (parallel/spill.py)",
     "chaos seam: SIGKILL this process when the host-stage step "
     "counter reaches N — rehearses a worker dying mid-spill so the "
     "checkpoint fallback path stays proven"),
    ("PYLOPS_MPI_TPU_METRICS", "off|on", "off",
     "diagnostics/metrics.py (solvers, collectives, resilience, "
     "tuning)",
     "fleet metrics registry; off is zero-cost no-op handles and the "
     "fused-solver HLO stays bit-identical"),
    ("PYLOPS_MPI_TPU_METRICS_FILE", "path",
     "unset (set by supervisor per worker)",
     "diagnostics/metrics.py (resilience/supervisor.py)",
     "periodic atomic JSON snapshot target of the metrics registry"),
    ("PYLOPS_MPI_TPU_METRICS_INTERVAL", "seconds", "5.0",
     "diagnostics/metrics.py",
     "snapshot-write cadence of the background metrics writer"),
    ("PYLOPS_MPI_TPU_BATCHED_CACHE", "int>=1", "8",
     "solvers/block.py",
     "batched_solve per-family compiled-executable LRU capacity "
     "(hit/miss counters: solver.batched.cache.*)"),
    ("PYLOPS_MPI_TPU_SERVE_QUEUE", "int>=1", "1024",
     "serving/queue.py",
     "admission-queue depth bound; a submit past it is rejected "
     "(QueueFull) — the serving backpressure knob"),
    ("PYLOPS_MPI_TPU_SERVE_WINDOW_MS", "milliseconds", "10.0",
     "serving/queue.py",
     "batch-formation window: how long the dispatcher holds an "
     "undersized batch open for late arrivals"),
    ("PYLOPS_MPI_TPU_SERVE_K_BUCKETS", "csv of ints", "1,2,4,8,16",
     "serving/engine.py",
     "block-width buckets the warm pool compiles and the packer "
     "rounds ragged fills up to"),
    ("PYLOPS_MPI_TPU_SERVE_DRAIN_TIMEOUT", "seconds", "30.0",
     "serving/service.py",
     "graceful-drain bound: how long SIGTERM/drain waits for "
     "in-flight batches before giving up"),
    ("PYLOPS_MPI_TPU_PRECOND", "none|jacobi|block_jacobi|mg", "none",
     "ops/precond.py",
     "default preconditioner kind make_precond builds when no "
     "explicit kind is passed (solvers stay unpreconditioned — and "
     "bit-identical — unless a call site opts in with M=)"),
    ("PYLOPS_MPI_TPU_MG_LEVELS", "int>=1", "3",
     "ops/precond.py",
     "V-cycle depth VCyclePrecond builds when levels= is not given "
     "(auto-reduced when grid divisibility runs out first)"),
    ("PYLOPS_MPI_TPU_REFINE", "0|1", "0",
     "resilience/driver.py",
     "iterative-refinement gate: resilient_solve turns "
     "precision-escalation restarts into narrow-inner-solve + "
     "wide-correction refinement passes instead of full wide "
     "re-solves"),
    ("PYLOPS_MPI_TPU_CA", "off|pipelined|sstep|auto", "off",
     "solvers/ca.py (solvers/basic.py, solvers/block.py, "
     "solvers/segmented.py)",
     "communication-avoiding Krylov tier: pipelined single-reduction "
     "PCG/PCGLS, s-step Gram mode, or latency-aware auto selection "
     "via the costmodel; off traces today's fused engines "
     "bit-identically"),
    ("PYLOPS_MPI_TPU_CA_S", "int>=2", "4",
     "solvers/ca.py (tuning/space.py)",
     "s-step depth of the CA solvers' Gram mode: one stacked "
     "reduction per s iterations at the price of 2s-1 operator "
     "applies; the monomial-basis conditioning guard falls back to "
     "the pipelined engine on breakdown"),
    ("PYLOPS_MPI_TPU_REDUCE_STALL", "int>=0", "unset (off)",
     "parallel/collectives.py (solvers)",
     "test/chaos seam: chain an N-step serial scalar dependency "
     "onto every solver reduction result so the CPU sim becomes "
     "latency-dominated like a pod fabric; unset/0 traces "
     "bit-identical programs"),
    ("PYLOPS_MPI_TPU_AOT", "auto|on|off", "off",
     "aot/store.py (solvers/basic.py, serving/engine.py)",
     "ahead-of-time executable tier for the fused solver programs: "
     "on lowers+compiles explicitly, serializes the executable "
     "(PJRT) into the bank, and replays it through the flat-call "
     "path on the next process start; auto arms only when AOT_CACHE "
     "is set; off (default) traces today's jit path bit-identically"),
    ("PYLOPS_MPI_TPU_AOT_CACHE", "directory", "unset (memory-only)",
     "aot/store.py",
     "on-disk bank for serialized executables (index.json + one blob "
     "per entry, schema-versioned, atomic, flock'd read-merge-write; "
     "rank 0 writes, other ranks read); unset under AOT=on keeps the "
     "bank process-local in memory"),
    ("PYLOPS_MPI_TPU_COMPILE_CACHE", "directory",
     "unset (entry scripts: <checkout>/.jax_cache; library: off)",
     "aot/compile_cache.py (package import)",
     "JAX persistent compilation cache dir — the fallback compile "
     "tier for programs the AOT bank does not serialize (closure "
     "operators, preconditioned solves, ISTA/FISTA); shared per CI "
     "job, rank-0-writes/others-read on multi-host. "
     "JAX_COMPILATION_CACHE_DIR, when set, stands instead and is "
     "never overridden"),
    ("PYLOPS_MPI_TPU_AUTODIFF", "off|on", "off",
     "utils/deps.py (solvers/basic.py, solvers/block.py, autodiff/*)",
     "differentiable-solver tier: on lets traced (jax.grad/jvp) "
     "inputs through cg/cgls/block_cg/block_cgls route to the "
     "implicit-diff custom_vjp rules (autodiff/implicit.py) instead "
     "of failing on the reverse-undifferentiable while_loop; off "
     "(default) leaves every solver entry and lowered program "
     "bit-identical — the explicit pylops_mpi_tpu.autodiff API "
     "works regardless of the knob"),
]


def knob_names():
    """Registered knob names (the set the registry test checks package
    reads against)."""
    return [row[0] for row in KNOBS]


def knob_table_markdown() -> str:
    """Render the registry as the markdown table embedded in
    docs/tpu.md ("Environment knobs") — regenerate the docs section
    with ``python -c "from pylops_mpi_tpu.utils.deps import
    knob_table_markdown; print(knob_table_markdown())"`` after adding
    a row."""
    lines = ["| knob | values | default | consumer | purpose |",
             "| --- | --- | --- | --- | --- |"]
    for name, values, default, consumer, purpose in KNOBS:
        lines.append(f"| `{name}` | `{values}` | {default} | "
                     f"{consumer} | {purpose} |")
    return "\n".join(lines)


def platform_override():
    return os.environ.get("PYLOPS_MPI_TPU_PLATFORM")


def explicit_stencil_enabled() -> bool:
    """Hand-scheduled shard_map (ring-halo ppermute + Pallas) stencil
    path for the axis-0 derivatives; set
    ``PYLOPS_MPI_TPU_EXPLICIT_STENCIL=0`` to force the implicit
    (GSPMD-partitioned) formulation."""
    return os.environ.get("PYLOPS_MPI_TPU_EXPLICIT_STENCIL", "1") != "0"


def x64_enabled() -> bool:
    return os.environ.get("PYLOPS_MPI_TPU_X64", "0") == "1"


def precond_default() -> str:
    """``PYLOPS_MPI_TPU_PRECOND`` — the preconditioner kind
    :func:`~pylops_mpi_tpu.ops.precond.make_precond` builds when the
    caller passes no explicit ``kind``."""
    return os.environ.get("PYLOPS_MPI_TPU_PRECOND", "none").strip() \
        .lower() or "none"


def mg_levels_default() -> int:
    """``PYLOPS_MPI_TPU_MG_LEVELS`` — V-cycle depth (floored at 1; a
    malformed value falls back to the default rather than breaking
    construction)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_MG_LEVELS", "3"))
    except ValueError:
        v = 3
    return max(1, v)


def refine_enabled() -> bool:
    """``PYLOPS_MPI_TPU_REFINE`` — when on, resilient_solve's
    precision-escalation restarts run as iterative-refinement passes
    (narrow inner solve + wide correction, resilience/driver.py)."""
    return os.environ.get("PYLOPS_MPI_TPU_REFINE", "0") == "1"


_warned_ca = False


def ca_mode() -> str:
    """``PYLOPS_MPI_TPU_CA`` resolved to ``off``/``pipelined``/
    ``sstep``/``auto`` (unknown values fall back to ``off`` with a
    one-time warning — a typo in a CI matrix must not silently swap
    solver engines)."""
    global _warned_ca
    m = os.environ.get("PYLOPS_MPI_TPU_CA", "off").strip().lower()
    if m in ("", "none", "default", "0", "classic"):
        m = "off"
    if m not in ("off", "pipelined", "sstep", "auto"):
        if not _warned_ca:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_CA={m!r} is not one of "
                "['off', 'pipelined', 'sstep', 'auto']; using 'off'",
                stacklevel=2)
            _warned_ca = True
        m = "off"
    return m


_warned_autodiff = False


def autodiff_mode() -> str:
    """``PYLOPS_MPI_TPU_AUTODIFF`` resolved to ``off``/``on`` (unknown
    values fall back to ``off`` with a one-time warning — a typo must
    not silently change which solver entries accept tracers)."""
    global _warned_autodiff
    m = os.environ.get("PYLOPS_MPI_TPU_AUTODIFF", "off").strip().lower()
    if m in ("", "none", "default", "0"):
        m = "off"
    if m in ("1", "true"):
        m = "on"
    if m not in ("off", "on"):
        if not _warned_autodiff:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_AUTODIFF={m!r} is not one of "
                "['off', 'on']; using 'off'", stacklevel=2)
            _warned_autodiff = True
        m = "off"
    return m


def autodiff_enabled() -> bool:
    """True when the differentiable-solver tier may reroute traced
    solver inputs (see :func:`autodiff_mode`)."""
    return autodiff_mode() == "on"


def ca_s_default() -> int:
    """``PYLOPS_MPI_TPU_CA_S`` — s-step depth of the CA solvers' Gram
    mode (floored at 2; a malformed value falls back to the default
    rather than breaking the solve)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_CA_S", "4"))
    except ValueError:
        v = 4
    return max(2, v)


def reduce_stall_steps() -> int:
    """``PYLOPS_MPI_TPU_REDUCE_STALL`` — serial-chain length appended
    to every solver reduction result (0/unset = off, bit-identical
    trace; malformed values are off rather than breaking the solve)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_REDUCE_STALL", "0"))
    except ValueError:
        v = 0
    return max(0, v)


_warned_overlap = False


def overlap_mode() -> str:
    """``PYLOPS_MPI_TPU_OVERLAP`` resolved to ``auto``/``on``/``off``
    (unknown values fall back to ``auto`` with a one-time warning — a
    typo in a CI matrix must not silently flip schedules)."""
    global _warned_overlap
    m = os.environ.get("PYLOPS_MPI_TPU_OVERLAP", "auto").strip().lower()
    if m in ("", "none", "default"):
        m = "auto"
    if m not in ("auto", "on", "off"):
        if not _warned_overlap:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_OVERLAP={m!r} is not one of "
                "['auto', 'on', 'off']; using 'auto'", stacklevel=2)
            _warned_overlap = True
        m = "auto"
    return m


def overlap_enabled(user=None) -> bool:
    """Resolve the pipelined-collectives tri-state to a bool. ``user``
    is a per-operator ``overlap=`` kwarg (``True``/``False``/
    ``"on"``/``"off"``/``"auto"``; ``None`` defers to the env).
    ``auto`` enables overlap only on real TPU backends: the ring /
    chunked schedules exist to hide ICI transfer behind compute, and on
    the CPU simulation they only add dispatch overhead while ``off``
    stays bit-identical to the bulk results."""
    if isinstance(user, bool):
        return user
    if user is None:
        mode = overlap_mode()
    else:
        mode = str(user).strip().lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"overlap={user!r}: expected 'auto', 'on', 'off', "
                "True or False")
    if mode == "on":
        return True
    if mode == "off":
        return False
    import jax
    return jax.default_backend() == "tpu"


def overlap_env_pinned() -> bool:
    """True when ``PYLOPS_MPI_TPU_OVERLAP`` is explicitly ``on`` or
    ``off`` — explicit env settings are user intent and beat the
    autotuner's plans, exactly like an explicit ``overlap=`` kwarg
    (``auto``/unset leaves the plan seam free to decide)."""
    return overlap_mode() in ("on", "off")


_warned_spill = False


def spill_mode() -> str:
    """``PYLOPS_MPI_TPU_SPILL`` resolved to ``auto``/``on``/``off``
    (unknown values fall back to ``auto`` with a one-time warning,
    same contract as :func:`overlap_mode`). ``off`` keeps the round-13
    planner refusal behavior bit-identical; ``auto`` (the default)
    converts ONLY moves the device planner would refuse into
    host-staged schedules — every currently-succeeding path keeps its
    device plan untouched; ``on`` forces host staging for every
    concrete cross-layout move (the CI rehearsal mode — traced moves
    never spill, a ``device_get`` needs a concrete array)."""
    global _warned_spill
    m = os.environ.get("PYLOPS_MPI_TPU_SPILL", "auto").strip().lower()
    if m in ("", "none", "default"):
        m = "auto"
    if m not in ("auto", "on", "off"):
        if not _warned_spill:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_SPILL={m!r} is not one of "
                "['auto', 'on', 'off']; using 'auto'", stacklevel=2)
            _warned_spill = True
        m = "auto"
    return m


_warned_hier = False


def hierarchical_mode() -> str:
    """``PYLOPS_MPI_TPU_HIERARCHICAL`` resolved to
    ``auto``/``on``/``off`` (unknown values fall back to ``auto`` with
    a one-time warning, same contract as :func:`overlap_mode`)."""
    global _warned_hier
    m = os.environ.get("PYLOPS_MPI_TPU_HIERARCHICAL",
                       "auto").strip().lower()
    if m in ("", "none", "default"):
        m = "auto"
    if m not in ("auto", "on", "off"):
        if not _warned_hier:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_HIERARCHICAL={m!r} is not one of "
                "['auto', 'on', 'off']; using 'auto'", stacklevel=2)
            _warned_hier = True
        m = "auto"
    return m


def hierarchical_enabled(user=None) -> bool:
    """Resolve the hierarchical-collectives tri-state to a bool.
    ``user`` is a per-operator ``hierarchical=`` kwarg (``True``/
    ``False``/``"on"``/``"off"``/``"auto"``; ``None`` defers to the
    env). ``auto`` enables the hierarchical schedules on real TPU
    backends and on CPU simulations that declare a fabric via
    ``PYLOPS_MPI_TPU_FABRIC`` — everywhere else ``off`` keeps the flat
    schedules bit-identical. A True result is still only *intent*: the
    schedules engage per operator only when the mesh is actually
    hybrid (``parallel.topology.is_hybrid``)."""
    if isinstance(user, bool):
        return user
    if user is None:
        mode = hierarchical_mode()
    else:
        mode = str(user).strip().lower()
        if mode not in ("auto", "on", "off"):
            raise ValueError(
                f"hierarchical={user!r}: expected 'auto', 'on', 'off', "
                "True or False")
    if mode == "on":
        return True
    if mode == "off":
        return False
    if os.environ.get("PYLOPS_MPI_TPU_FABRIC", "").strip():
        return True
    import jax
    return jax.default_backend() == "tpu"


def hierarchical_env_pinned() -> bool:
    """True when ``PYLOPS_MPI_TPU_HIERARCHICAL`` is explicitly ``on``
    or ``off`` — explicit env settings beat the autotuner's plans,
    same precedence rule as :func:`overlap_env_pinned`."""
    return hierarchical_mode() in ("on", "off")


def comm_chunks_env_pinned() -> bool:
    """True when ``PYLOPS_MPI_TPU_COMM_CHUNKS`` is explicitly set
    (even to the default value) — same tuner-precedence rule as
    :func:`overlap_env_pinned`."""
    return "PYLOPS_MPI_TPU_COMM_CHUNKS" in os.environ


def batch_default() -> int:
    """Default RHS-column count ``K`` of the batched solve paths
    (``PYLOPS_MPI_TPU_BATCH``, default 1 = single-RHS; floored at 1).
    Forwarded into plan-cache contexts (``extra["batch"]``) so a plan
    measured at one block width is never replayed at another."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_BATCH", "1"))
    except ValueError:
        v = 1
    return max(1, v)


def comm_chunks_default() -> int:
    """Default chunk count for the streamed pencil transposes
    (``PYLOPS_MPI_TPU_COMM_CHUNKS``, default 4; floored at 1)."""
    try:
        v = int(os.environ.get("PYLOPS_MPI_TPU_COMM_CHUNKS", "4"))
    except ValueError:
        v = 4
    return max(1, v)


def matmul_precision():
    """``jax_default_matmul_precision`` to pin at import (see module
    docstring); ``default``/empty leaves JAX's backend default."""
    p = os.environ.get("PYLOPS_MPI_TPU_MATMUL_PRECISION", "highest")
    return None if p in ("", "default") else p


_applied = False


def apply_environment() -> None:
    """Apply env-flag configuration to JAX (idempotent; call before any
    jnp op if overriding the platform)."""
    global _applied
    if _applied:
        return
    import jax
    plat = platform_override()
    if plat:
        jax.config.update("jax_platforms", plat)
    if x64_enabled():
        jax.config.update("jax_enable_x64", True)
    prec = matmul_precision()
    if prec is not None:
        jax.config.update("jax_default_matmul_precision", prec)
    _applied = True
