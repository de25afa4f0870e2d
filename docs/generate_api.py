"""Generate the markdown API reference from live docstrings.

``python docs/generate_api.py`` rewrites ``docs/api/*.md`` — one page
per section, mirroring the reference's ``docs/source/api/index.rst``
grouping — from the package's actual signatures and docstrings (which
carry the reference ``file:line`` citations). Regenerate after adding
a public symbol; ``tests/test_docs.py`` fails if a page goes stale or
a top-level symbol is missing from the reference.
"""

import importlib
import inspect
import os
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

OUT = os.path.join(ROOT, "docs", "api")

# page -> [(section title, module path, [symbol, ...]), ...]
PAGES = {
    "distributedarray": [
        ("Distributed arrays", "pylops_mpi_tpu",
         ["Partition", "DistributedArray", "StackedDistributedArray",
          "local_split"]),
    ],
    "mesh": [
        ("Device meshes", "pylops_mpi_tpu.parallel.mesh",
         ["make_mesh", "make_mesh_2d", "make_mesh_hybrid",
          "initialize_multihost", "default_mesh", "set_default_mesh",
          "best_grid_2d", "local_device_count"]),
        ("Explicit collectives", "pylops_mpi_tpu.parallel.collectives",
         ["all_to_all_resharding", "ring_halo_extend", "cart_halo_extend",
          "halo_slab", "ring_pass", "hier_pencil_transpose",
          "hier_psum_scatter", "hier_all_gather"]),
        ("Bounded-memory resharding planner",
         "pylops_mpi_tpu.parallel.reshard",
         ["Layout", "ReshardStep", "ReshardPlan", "ReshardError",
          "reshard_budget", "plan_reshard", "reshard", "place_replica",
          "reshard_raw"]),
        ("Host-RAM spill tier", "pylops_mpi_tpu.parallel.spill",
         ["HostArray", "to_host", "reshard_from_host", "run_spilled"]),
        ("Fabric topology", "pylops_mpi_tpu.parallel.topology",
         ["fabric_override", "axis_fabric", "mesh_fabrics", "is_hybrid",
          "hybrid_axes", "topology_key", "collective_fabric", "slice_map",
          "slice_run", "perm_crossings"]),
    ],
    "operators": [
        ("Templates", "pylops_mpi_tpu",
         ["MPILinearOperator", "MPIStackedLinearOperator",
          "aslinearoperator"]),
        ("Basic operators", "pylops_mpi_tpu",
         ["MPIMatrixMult", "MPIBlockDiag", "MPIStackedBlockDiag",
          "MPIVStack", "MPIStackedVStack", "MPIHStack", "MPIHalo",
          "halo_block_split"]),
        ("Matmul grid helpers", "pylops_mpi_tpu.basicoperators",
         ["active_grid_comm", "local_block_split", "block_gather"]),
        ("Derivatives", "pylops_mpi_tpu",
         ["MPIFirstDerivative", "MPISecondDerivative", "MPILaplacian",
          "MPIGradient"]),
        ("Signal processing", "pylops_mpi_tpu",
         ["MPIFredholm1", "MPINonStationaryConvolve1D", "MPIFFT2D",
          "MPIFFTND"]),
        ("Wave-equation processing", "pylops_mpi_tpu", ["MPIMDC"]),
        ("Preconditioners", "pylops_mpi_tpu",
         ["JacobiPrecond", "BlockJacobiPrecond", "VCyclePrecond",
          "make_precond"]),
        ("Diagonal probing", "pylops_mpi_tpu.ops.precond",
         ["probe_diagonal"]),
        ("Sparse tier", "pylops_mpi_tpu",
         ["MPISparseMatrixMult", "auto_sparse_matmult"]),
    ],
    "solvers": [
        ("Basic", "pylops_mpi_tpu",
         ["cg", "cgls", "CG", "CGLS", "clear_fused_cache"]),
        ("Sparsity", "pylops_mpi_tpu", ["ista", "fista", "ISTA", "FISTA"]),
        ("Guarded (explicit status word)", "pylops_mpi_tpu.solvers",
         ["cg_guarded", "cgls_guarded", "ista_guarded", "fista_guarded"]),
        ("Segmented (checkpoint/resume)", "pylops_mpi_tpu",
         ["cg_segmented", "cgls_segmented"]),
        ("Batched (block-Krylov and vmap-over-parameters)",
         "pylops_mpi_tpu",
         ["block_cg", "block_cgls", "block_cg_segmented",
          "batched_solve", "batched_cache_info"]),
        ("Communication-avoiding (pipelined / s-step)",
         "pylops_mpi_tpu.solvers.ca",
         ["resolve_mode", "ca_reductions_per_iter",
          "classic_reductions_per_iter", "last_fallback"]),
        ("Eigenvalues", "pylops_mpi_tpu", ["power_iteration"]),
    ],
    "resilience": [
        ("Status word and guards", "pylops_mpi_tpu.resilience.status",
         ["status_name", "guards_mode", "guards_enabled", "stall_window",
          "last_status"]),
        ("Escalation driver", "pylops_mpi_tpu.resilience",
         ["resilient_solve", "ResilientResult"]),
        ("Iterative refinement", "pylops_mpi_tpu.resilience",
         ["refined_solve", "RefinedResult"]),
        ("Bounded retry", "pylops_mpi_tpu.resilience.retry",
         ["retry_call", "default_retries", "default_backoff_s",
          "default_jitter"]),
        ("Heartbeats and collective watchdogs",
         "pylops_mpi_tpu.resilience.elastic",
         ["elastic_initialize", "worker_config", "WorkerConfig",
          "maybe_start_heartbeat", "start_heartbeat", "stop_heartbeat",
          "HeartbeatWriter", "read_heartbeat", "heartbeat_interval",
          "watched_call", "WatchdogTimeout", "watchdog_mode",
          "watchdog_enabled", "watchdog_timeout",
          "request_drain", "drain_requested", "reset_drain",
          "install_sigterm_drain"]),
        ("Job supervisor (launch, classify, shrink, relaunch)",
         "pylops_mpi_tpu.resilience.supervisor",
         ["launch_job", "JobResult", "Failure", "WorkerHandle",
          "free_port"]),
        ("In-place (no-checkpoint) elastic recovery",
         "pylops_mpi_tpu.resilience.elastic",
         ["ElasticReconfig", "inplace_mode", "inplace_armed",
          "quorum_fraction", "reconfig_file", "pending_reconfig",
          "apply_reconfig", "reform_mesh", "bank_carry", "banked_carry",
          "clear_carry", "restore_carry"]),
        ("Fault injection (chaos seams)",
         "pylops_mpi_tpu.resilience.faults",
         ["arm", "disarm", "armed", "consume", "fault_signature",
          "host_stall", "corrupt_plan_cache", "flaky",
          "maybe_kill_reshard", "reset_reshard_steps", "reshard_steps"]),
    ],
    "local": [
        ("Local (per-shard) operators", "pylops_mpi_tpu.ops.local",
         ["LocalOperator", "MatrixMult", "Identity", "Diagonal", "Zero",
          "Transpose", "Roll", "Flip", "Pad", "FunctionOperator",
          "FirstDerivative", "SecondDerivative", "Laplacian", "VStack",
          "HStack", "BlockDiag", "FFT", "Conv1D",
          "NonStationaryConvolve1D"]),
        ("Pallas TPU kernels", "pylops_mpi_tpu.ops.pallas_kernels",
         ["first_derivative_centered", "second_derivative", "stencil_taps",
          "batched_normal_matvec", "normal_matvec_supported",
          "pallas_available"]),
        ("Local FFT engine", "pylops_mpi_tpu.ops.dft",
         ["fft", "ifft", "rfft", "irfft", "fft_mode", "set_fft_mode",
          "use_matmul_fft", "resolved_mode", "fft_planes", "ifft_planes",
          "rfft_planes", "irfft_planes"]),
    ],
    "utils": [
        ("Testing", "pylops_mpi_tpu.utils.dottest", ["dottest"]),
        ("Benchmarking / profiling", "pylops_mpi_tpu.utils.benchmark",
         ["benchmark", "mark", "profile_trace", "time_callable"]),
        ("Collective-schedule inspection", "pylops_mpi_tpu.utils.hlo",
         ["collective_report", "assert_no_full_gather",
          "parse_hlo_collectives", "count_collectives",
          "assert_ring_schedule", "count_host_callbacks",
          "assert_no_host_callbacks"]),
        ("Checkpointing", "pylops_mpi_tpu.utils.checkpoint",
         ["save_solver", "load_solver", "save_fused_carry",
          "load_fused_carry"]),
        ("FFT helpers", "pylops_mpi_tpu.utils.fft_helper",
         ["fftshift_nd", "ifftshift_nd"]),
        ("Decorators", "pylops_mpi_tpu.utils.decorators", ["reshaped"]),
        ("Feature flags", "pylops_mpi_tpu.utils.deps",
         ["platform_override", "explicit_stencil_enabled", "x64_enabled",
          "matmul_precision", "apply_environment", "hierarchical_mode",
          "hierarchical_enabled"]),
        ("Native host runtime", "pylops_mpi_tpu.native",
         ["available", "pack_padded", "unpack_padded", "read_binary",
          "write_binary", "write_binary_at", "local_split_native"]),
        ("Plotting", "pylops_mpi_tpu.plotting.plotting",
         ["plot_distributed_array", "plot_local_arrays"]),
    ],
    "diagnostics": [
        ("Structured tracing", "pylops_mpi_tpu.diagnostics.trace",
         ["trace_mode", "trace_enabled", "span", "op_span", "event",
          "counter", "get_events", "clear_events", "dump", "span_tree"]),
        ("Cost models and roofline",
         "pylops_mpi_tpu.diagnostics.costmodel",
         ["OpCost", "estimate", "register_cost", "roofline",
          "summa_comm_volume", "summa_comm_volume_split",
          "pencil_transpose_cost", "peak_flops",
          "peak_hbm_gbps", "peak_ici_gbps", "device_peaks"]),
        ("In-loop solver telemetry",
         "pylops_mpi_tpu.diagnostics.telemetry",
         ["telemetry_enabled", "telemetry_signature", "iteration",
          "history", "clear_history"]),
        ("Stage budgets and the deadline runner",
         "pylops_mpi_tpu.diagnostics.profiler",
         ["stage_budget", "DeadlineRunner"]),
        ("Fleet metrics registry",
         "pylops_mpi_tpu.diagnostics.metrics",
         ["metrics_mode", "metrics_enabled", "metrics_file",
          "metrics_interval", "inc", "set_gauge", "observe", "timer",
          "snapshot", "clear_metrics", "write_snapshot",
          "read_snapshot", "hist_quantiles"]),
        ("Cross-worker trace aggregation",
         "pylops_mpi_tpu.diagnostics.aggregate",
         ["load_events", "guess_rank", "collective_entries",
          "align_offsets", "merge_traces", "critical_path",
          "discover_trace_files", "aggregate_files"]),
    ],
    "tuning": [
        ("Plan seam", "pylops_mpi_tpu.tuning.plan",
         ["Plan", "get_plan", "tune_mode", "tune_enabled", "plan_key",
          "shape_bucket", "applied_provenance"]),
        ("Tuning spaces", "pylops_mpi_tpu.tuning.space",
         ["Axis", "TuningSpace", "register_space", "space_for",
          "candidates", "rank", "default_params"]),
        ("Measured search", "pylops_mpi_tpu.tuning.search",
         ["measure_candidates", "tune_budget_s", "tune_topk",
          "tune_margin"]),
        ("Plan cache", "pylops_mpi_tpu.tuning.cache",
         ["cache_path", "lookup", "store", "load_plans",
          "clear_memory"]),
    ],
    "serving": [
        ("Warm-executable pool", "pylops_mpi_tpu.serving.engine",
         ["k_buckets", "bucket_for", "FamilySpec", "BlockOutcome",
          "WarmPool"]),
        ("Admission queue and continuous batcher",
         "pylops_mpi_tpu.serving.queue",
         ["queue_bound", "batch_window_s", "QueueFull", "Ticket",
          "SolveRequest", "AdmissionQueue", "pack", "Dispatcher"]),
        ("Durable request spool", "pylops_mpi_tpu.serving.spool",
         ["init_spool", "enqueue", "claim", "complete", "fail",
          "recover_claimed", "read_result", "result_ids",
          "pending_count", "claimed_count", "request_drain",
          "drain_requested", "Claim"]),
        ("Serve-forever deployment", "pylops_mpi_tpu.serving.service",
         ["drain_timeout_s", "SolveDaemon", "worker_main",
          "serve_job"]),
    ],
    "aot": [
        ("AOT executable bank", "pylops_mpi_tpu.aot",
         ["aot_mode", "aot_enabled", "bank_dir", "load_index",
          "store_entry", "lookup", "rank_writes", "clear_memory"]),
        ("Signatures", "pylops_mpi_tpu.aot",
         ["compile_signature", "op_signature"]),
        ("Serialization and replay", "pylops_mpi_tpu.aot",
         ["AotExecutable", "serialize_compiled", "load_serialized",
          "compile_count", "reset_compile_count"]),
        ("Persistent compilation cache (fallback layer)",
         "pylops_mpi_tpu.aot",
         ["maybe_enable_compile_cache", "compile_cache_dir"]),
    ],
    "autodiff": [
        ("Operator rules (adjoint VJP/JVP)", "pylops_mpi_tpu.autodiff",
         ["make_differentiable", "DifferentiableOperator"]),
        ("Rule internals", "pylops_mpi_tpu.autodiff.rules",
         ["transpose_apply", "param_cotangent", "zero_op_cotangent"]),
        ("Implicit differentiation through the fused solvers",
         "pylops_mpi_tpu.autodiff",
         ["cg_solve", "cgls_solve", "block_cg_solve",
          "block_cgls_solve"]),
        ("Unrolled (scan-tape) oracles", "pylops_mpi_tpu.autodiff",
         ["unrolled_cg", "unrolled_cgls"]),
        ("Training driver", "pylops_mpi_tpu.autodiff",
         ["fit", "trainable_leaves", "param_count"]),
    ],
    "models": [
        ("Model workflows", "pylops_mpi_tpu.models",
         ["PoststackLinearModelling", "MPIPoststackLinearModelling",
          "poststack_regularized", "poststack_inversion", "MPILSM", "KirchhoffDemigration",
          "TravelTimeSpray", "kernel_to_frequency", "ricker"]),
        ("Multi-dimensional deconvolution", "pylops_mpi_tpu.models.mdd",
         ["mdd"]),
    ],
}

PAGE_TITLES = {
    "distributedarray": "Distributed arrays",
    "mesh": "Meshes and collectives",
    "operators": "Distributed operators",
    "solvers": "Solvers",
    "local": "Local operators and kernels",
    "utils": "Utilities",
    "diagnostics": "Diagnostics and observability",
    "resilience": "Resilience and fault injection",
    "tuning": "Autotuning",
    "serving": "Serving (always-on solve service)",
    "aot": "Ahead-of-time compile tier",
    "autodiff": "Differentiable operator layer",
    "models": "Model workflows",
}


def _sig(obj) -> str:
    import enum
    try:
        if inspect.isclass(obj) and issubclass(obj, enum.Enum):
            return obj.__name__
        if inspect.isclass(obj):
            return f"{obj.__name__}{inspect.signature(obj.__init__)}" \
                .replace("(self, ", "(").replace("(self)", "()")
        return f"{obj.__name__}{inspect.signature(obj)}"
    except (TypeError, ValueError):
        return obj.__name__


def _doc(obj) -> str:
    # vars() check: inspect.getdoc inherits base-class docstrings, which
    # would render e.g. the generic Enum tutorial for Partition
    if inspect.isclass(obj) and not vars(obj).get("__doc__"):
        import enum
        if issubclass(obj, enum.Enum):
            members = ", ".join(f"`{m.name}`" for m in obj)
            return f"Enum members: {members}."
        return "*(no docstring)*"
    d = inspect.getdoc(obj)
    return d.strip() if d else "*(no docstring)*"


def _methods(cls):
    """Public methods/properties documented on the class itself."""
    out = []
    for name, m in sorted(vars(cls).items()):
        if name.startswith("_"):
            continue
        if isinstance(m, property):
            if m.fget and m.fget.__doc__:
                out.append((name + " (property)", inspect.getdoc(m.fget)))
        elif callable(m) and m.__doc__:
            try:
                sig = str(inspect.signature(m)).replace("(self, ", "(") \
                    .replace("(self)", "()")
            except (TypeError, ValueError):
                sig = "(...)"
            out.append((name + sig, inspect.getdoc(m)))
    return out


def render_page(key, sections) -> str:
    lines = [f"# {PAGE_TITLES[key]}", "",
             "<!-- generated by docs/generate_api.py - do not edit -->", ""]
    for title, modpath, symbols in sections:
        mod = importlib.import_module(modpath)
        lines += [f"## {title}", "", f"Module: `{modpath}`", ""]
        for s in symbols:
            obj = getattr(mod, s)
            lines += [f"### `{_sig(obj)}`", ""]
            lines += [_doc(obj), ""]
            if inspect.isclass(obj):
                meths = _methods(obj)
                if meths:
                    lines += ["**Methods**", ""]
                    for mname, mdoc in meths:
                        first = mdoc.split("\n\n")[0].replace("\n", " ")
                        lines += [f"- `{mname}` — {first}"]
                    lines += [""]
    return "\n".join(lines) + "\n"


def main() -> None:
    os.makedirs(OUT, exist_ok=True)
    index = ["# API reference", "",
             "<!-- generated by docs/generate_api.py - do not edit -->", "",
             "Grouped as the reference's `docs/source/api/index.rst`; every",
             "entry's docstring cites the `pylops_mpi` source it rebuilds.",
             ""]
    for key, sections in PAGES.items():
        path = os.path.join(OUT, f"{key}.md")
        with open(path, "w") as f:
            f.write(render_page(key, sections))
        nsyms = sum(len(s[2]) for s in sections)
        index.append(f"- [{PAGE_TITLES[key]}]({key}.md) — {nsyms} symbols")
        print(f"wrote {path} ({nsyms} symbols)")
    with open(os.path.join(OUT, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")


if __name__ == "__main__":
    main()
