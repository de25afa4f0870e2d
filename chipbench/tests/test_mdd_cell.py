"""Tests of what PR 34 added to the benchmark: the cell
``mdd_obc.cgls_nv16`` rehearsed on the CPU; its three layer readers on
a synthesized trace; the cost functions; the loop's comparison refusing
the bfloat16-product control; the configuration's file; and "files and
entries only" — the cell's files laid over a copy of the benchmark as
it was before them. The manifest lint and the rehearsal of every cell
are ``test_chipbench.py``'s, which pick the cell up from the registry.
No chip needed."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import costs, costs_mdd, scope_time  # noqa: E402
from chipbench.layers import (fft_device_ms,  # noqa: E402
                              fredholm_device_ms, fredholm_roofline_pct,
                              operator_device_ms)
from chipbench.tests.test_chipbench import (check_rehearsal,  # noqa: E402
                                            registry, run_cell, sources_of)
from chipbench.tests.test_program_trace import (DEV, ctx_of,  # noqa: E402
                                                solved)

CELL = "mdd_obc.cgls_nv16"
NEW_FILES = ["configs/mdd_obc.json", "builders/mdd.py", "costs_mdd.py",
             "loops/closed_broadcast.py", "traffic/cgls_nv16.json",
             "layers/fredholm_device_ms.py", "layers/fft_device_ms.py",
             "layers/fredholm_roofline_pct.py", "tests/test_mdd_cell.py",
             "scratch/mdd_probe.py", "scratch/mdd_control.py",
             "scratch/compile_mdd_topology.py"]
BODY = "jit(f)/while/body/pmt._ProductLinearOperator.matvec/"
PEAK = {"bf16_flops_per_s": 197e12, "f32_passes": 6,
        "hbm_bytes_per_s": 819e9}
SIZES = {"nfmax": 64, "ns": 4096, "nr": 4096, "nt": 1023, "nv": 16}


def chain(scoped=True):
    """Two solves of two iterations of the MDC chain; slice 0..10000.
    An iteration: 300 under the FFTs, 500 under the Fredholm product,
    40 of the chain's own (slice, pad, scale), 200 of the solver's."""
    def op(name, at, dur, path):
        return (name, at, dur, None, {"tf_op": path} if scoped else None)

    ops = []
    for t in (0, 5000):
        ops.append(("%while.3 = () while()", t + 1200, 3700))
        at = t + 1300
        for i in range(2):
            for name, dur, path in (
                    ("%fusion.1 = f32[] fusion()", 300,
                     BODY + "pmt.MPILinearOperator.matvec/pmt.local.FFT/fft"),
                    ("%fusion.2 = f32[] fusion()", 500,
                     BODY + "pmt._ScaledLinearOperator.matvec/"
                     "pmt.MPIFredholm1.matvec/kxy,kyz->kxz/dot_general"),
                    ("%fusion.3 = f32[] fusion()", 40,
                     BODY + "pmt._ScaledLinearOperator.matvec/mul"),
                    ("%fusion.5 = f32[] fusion()", 200,
                     "jit(f)/while/body/add")):
                ops.append(op(name, at, dur, path))
                at += dur
    host = [("cb.slice", 0, 10000), ("cb.solve", 1000, 4000),
            ("cb.solve", 6000, 3900),
            ("pmt.solver.cgls", 1100, 3890), ("pmt.solver.cgls", 6100, 3790)]
    return [(DEV, [("XLA Ops", ops)]), ("/host:CPU", [("main", host)])]


def test_the_cell_is_registered_as_the_issue_asks():
    b = registry()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("mdd_obc", "cgls_nv16", 1)
    conf = {c["name"]: c for c in b["configs"]}["mdd_obc"]
    assert conf["reduced"] == ["nfmax", "niter"]
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"latency_p50_ms", "iter_device_ms", "loop_gap_pct",
                      "iter_roofline_pct", "between_solves_ms",
                      "operator_device_ms", "solver_self_device_ms",
                      "fredholm_device_ms", "fft_device_ms",
                      "fredholm_roofline_pct"}
    for name in ("fredholm_device_ms", "fft_device_ms",
                 "fredholm_roofline_pct"):
        m = {m["name"]: m for m in b["per_layer"]}[name]
        assert m["workloads"] == [CELL] and m["layer"] == "Ops and kernels"
        assert m["moves"] == "latency_p50_ms"


def test_the_entries_have_the_manifests_form():
    """The rules of form that ``test_chipbench.py``'s lint does not hold
    a configuration to (its ``why`` was 203 characters once: refused
    before any run): every free text of every entry 1 to 200 printable
    characters on one line, every entry just the keys of its kind."""
    b = registry()
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    for kind, allowed in keys.items():
        for e in b[kind]:
            assert set(e) - {"workloads"} == allowed - {"workloads"}, e
            for k in ("why", "source", "layer"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200, (e["name"], k, len(e[k]))
                    assert e[k].isprintable() and e[k].isascii()
            assert len(e["name"]) <= 64 and e["name"].isascii()
    for w in b["command"]:
        assert 1 <= len(w) <= 200
    for c in b["configs"]:
        assert len(c["reduced"]) <= 16


def test_time_under_the_fredholm_and_fft_scopes(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, chain(), {"iterations_per_solve": 2})
    assert fft_device_ms.read(ctx) == pytest.approx(300 / 1e6)
    assert fredholm_device_ms.read(ctx) == pytest.approx(500 / 1e6)
    # the accepted reader's whole: those two and the chain's own ops
    assert operator_device_ms.read(ctx) == pytest.approx(840 / 1e6)
    assert scope_time.under(ctx, "pmt.local.Conv1D") is None


def test_fredholm_roofline_is_the_floor_over_the_scopes_time(tmp_path,
                                                             monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, chain(), {"iterations_per_solve": 2})
    ctx["peaks"] = PEAK
    ctx["deployment"] = SimpleNamespace(
        dtype="float32", fredholm_cost=lambda: costs_mdd.fredholm(SIZES))
    floor_ms = 1e3 * (8 * 64 * 4096 ** 2 + 4 * 8 * 64 * 4096 * 16) / 819e9
    assert fredholm_roofline_pct.read(ctx) == pytest.approx(
        100.0 * floor_ms / (500 / 1e6))
    assert any("bytes bind" in m for m in ctx["said"])
    ctx["deployment"] = SimpleNamespace(dtype="float32")   # no such product
    assert fredholm_roofline_pct.read(ctx) is None


@pytest.mark.parametrize("planes", [chain(scoped=False), solved()],
                         ids=["unnamed-program", "no-such-scopes"])
def test_a_program_without_the_scopes_reads_nothing(tmp_path, monkeypatch,
                                                    planes):
    """What the parent gives: nothing, and no exception."""
    ctx = ctx_of(tmp_path, monkeypatch, planes, {"iterations_per_solve": 2})
    ctx["peaks"] = PEAK
    ctx["deployment"] = SimpleNamespace(
        dtype="float32", fredholm_cost=lambda: costs_mdd.fredholm(SIZES))
    untraced = {"trace": None, "cell": {"name": "x"}, "records": {},
                "peaks": PEAK, "deployment": ctx["deployment"],
                "log": print}
    for reader in (fredholm_device_ms, fft_device_ms, fredholm_roofline_pct):
        assert reader.read(ctx) is None
        assert reader.read(untraced) is None


def test_the_costs_are_the_issues_floor():
    """The kernel ONCE an iteration at its stored 8 bytes and four
    268 MB vector streams: 9.66 GB, 11.8 ms, bytes bind; both products
    2 x 1.37e11 flops, 8.4 ms at ``highest``."""
    it = costs_mdd.iteration(SIZES)
    assert it["bytes"] == 8589934592 + 4 * 268173312 == 9662627840
    floor = costs.least_seconds(it, PEAK, "float32")
    assert floor["binds"] == "bytes"
    assert 1e3 * floor["seconds"] == pytest.approx(11.8, abs=0.01)
    fr = costs_mdd.fredholm(SIZES)
    assert fr["flops"] == 2 * 8 * 64 * 4096 ** 2 * 16
    assert fr["bytes"] == 8589934592 + 4 * 33554432
    f = costs.least_seconds(fr, PEAK, "float32")
    assert f["binds"] == "bytes"
    assert 1e3 * f["flops_s"] == pytest.approx(8.37, abs=0.01)
    # two honest sweeps of the kernel read under half of it
    two = 1e3 * (2 * 8589934592 + 4 * 33554432) / 819e9
    assert 45 < 100 * 1e3 * f["seconds"] / two < 52


def test_the_loops_comparison_refuses_bf16_products():
    """The control: a plain solve whose Fredholm products round both
    operands to bfloat16 stands in for the program in the cell's own
    loop (tiny, on the CPU) and comes out as not correct by
    ``rel_tol`` — the warm-up stops, no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "chipbench", "scratch", "mdd_control.py"),
         "bf16", "--workload", CELL, "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode != 0
    assert "CONTROL" in proc.stderr
    assert "RuntimeError: warm-up: rel_tol" in proc.stderr, \
        proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout


def test_judge_is_the_whole_comparison():
    from chipbench.loops.closed_broadcast import judge
    limits = {"rel_tol": 1e-4, "resid_drop": 0.5}
    assert judge({"rel_tol": 2e-6, "resid_drop": 0.01}, limits) == []
    assert judge({"rel_tol": 2e-3}, limits) == ["rel_tol"]
    assert judge({"rel_tol": float("nan")}, limits) == ["rel_tol"]
    assert judge({"resid_drop": 0.6}, limits) == ["resid_drop"]


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mdd_obc.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["nfmax", "niter"]
    assert set(cfg["reduced_why"]) == {"nfmax", "niter"}
    s = cfg["sizes"]
    assert (s["ns"], s["nr"], s["nt"], s["nv"]) == (4096, 4096, 1023, 16)
    assert s["nfmax_deployment"] == 256 and s["nfmax"] % 16 == 0
    assert s["nfmax"] <= 64 and s["dt"] == 0.004 and s["f0"] == 20.0
    assert {"survey", "nv", "family", "response", "x0", "d"} \
        <= set(cfg["assumed"])
    assert "condition" in cfg["assumed"]["family"].lower()
    g = cfg["guarantees"]
    assert g["niter"] == 30 and 0 < g["resid_drop"] < 1
    assert 1e-6 <= g["rel_tol"] < 2e-3      # under the control's reading
    assert {"rel_tol_why", "resid_drop_why"} <= set(g)
    assert "bfloat16" in g["rel_tol_why"] and "ill-posed" in g["text"]
    assert "PLACEHOLDER" not in json.dumps(cfg)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "cgls_nv16.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed_broadcast" and mix["pool"] == 2
    assert mix["niter"] == 30
    assert mix["trace"] == {"pre_s": 2.0, "slice_s": 12.0}
    assert "PLACEHOLDER" not in json.dumps(mix)


def test_the_cell_is_files_and_entries_only(tmp_path):
    """The benchmark as it was before PR 34 (this cell's files taken
    out of a copy, its entries out of the registry) plus the files and
    the appended entries IS the benchmark now; no other file differs,
    and the cell rehearses in the copy."""
    import shutil
    bench = os.path.join(ROOT, "chipbench")
    root = tmp_path / "checkout"
    shutil.copytree(bench, root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "pylops_mpi_tpu"),
               root / "pylops_mpi_tpu")
    now = registry()
    was = json.loads(json.dumps(now))
    was["configs"] = [c for c in was["configs"] if c["name"] != "mdd_obc"]
    was["workloads"] = [w for w in was["workloads"] if w["name"] != CELL]
    was["per_layer"] = [m for m in was["per_layer"]
                        if m.get("workloads") != [CELL]]
    for m in was["end_to_end"] + was["per_layer"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL     # appended, at the end
            m["workloads"].remove(CELL)
    # every list only GREW, at its end
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [e["name"] for e in now[key]][:len(was[key])] \
            == [e["name"] for e in was[key]]
    assert len(now["configs"]) == len(was["configs"]) + 1
    assert len(now["workloads"]) == len(was["workloads"]) + 1
    assert len(now["per_layer"]) == len(was["per_layer"]) + 3
    assert (now["command"], now["paths"], now["run_seconds"]) \
        == (was["command"], was["paths"], was["run_seconds"])
    # without the new files the old cells' files are all still there
    for rel in NEW_FILES:
        assert os.path.exists(os.path.join(bench, rel)), rel
    (root / "BENCHMARK.json").write_text(json.dumps(now))
    last = check_rehearsal(run_cell(str(root), CELL, 1), sources_of(now))
    assert last["metrics"]["compiles_in_window"]["value"] == 0
