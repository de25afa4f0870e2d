"""Tests of what PR 38 added to the benchmark: the cell
``lsm_kirchhoff.cgls_shots8`` rehearsed on the CPU; its two layer
readers on a synthesized trace; the cost functions; the loop's
comparison (``loops/closed_vstack.py``) refusing the bfloat16-product
control; the account of what float32 determines; the configuration's
file; and "files and entries only" — the registry held to "at least"
and "in order", never to an exact tail, so that a later PR's appended
entries do not fail it. The rehearsal of every cell is
``test_chipbench.py``'s, which picks the cell up from the registry. No
chip needed."""

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

from chipbench import costs, costs_lsm, scope_time  # noqa: E402
from chipbench.layers import (kirchhoff_device_ms,  # noqa: E402
                              kirchhoff_roofline_pct, operator_device_ms)
from chipbench.tests.test_chipbench import (check_rehearsal,  # noqa: E402
                                            registry, run_cell, sources_of)
from chipbench.tests.test_program_trace import (DEV, ctx_of,  # noqa: E402
                                                solved)

CONFIG, CELL = "lsm_kirchhoff", "lsm_kirchhoff.cgls_shots8"
NEW_FILES = ["configs/lsm_kirchhoff.json", "builders/lsm.py", "costs_lsm.py",
             "traffic/cgls_shots8.json", "loops/closed_vstack.py",
             "layers/kirchhoff_device_ms.py",
             "layers/kirchhoff_roofline_pct.py", "tests/test_lsm_cell.py",
             "scratch/lsm_probe.py", "scratch/lsm_control.py",
             "scratch/lsm_account.py", "scratch/compile_lsm_topology.py"]
NEW_METRICS = ["kirchhoff_device_ms", "kirchhoff_roofline_pct"]
ACCEPTED_LISTS = ["latency_p50_ms", "iter_device_ms", "loop_gap_pct",
                  "iter_roofline_pct", "between_solves_ms",
                  "operator_device_ms", "solver_self_device_ms",
                  "solver_update_device_ms", "solver_cost_device_ms",
                  "unscoped_device_ms", "launch_host_ms"]
BODY = "jit(f)/while/body/pmt.MPIVStack.matvec/"
PEAK = {"bf16_flops_per_s": 197e12, "f32_passes": 6,
        "hbm_bytes_per_s": 819e9}
SIZES = {"ns": 8, "nr": 256, "nz": 512, "nx": 1024, "nt": 1024, "nwav": 81}


def stack(scoped=True):
    """Two solves of two iterations of the stacked demigration; slice
    0..10000. An iteration: 700 under the spray (its kernel and a pad
    beside it), 60 under the wavelet, 20 of the stack's own
    (concatenate), 200 of the solver's."""
    def op(name, at, dur, path):
        return (name, at, dur, None, {"tf_op": path} if scoped else None)

    ops = []
    for t in (0, 5000):
        ops.append(("%while.3 = () while()", t + 1200, 3700))
        at = t + 1300
        for i in range(2):
            for name, dur, path in (
                    ("%pmt_kirchhoff.1 = f32[] custom-call()", 650,
                     BODY + "pmt.local.TravelTimeSpray/jit(kirchhoff_spray)"
                     "/pmt_kirchhoff"),
                    ("%fusion.1 = f32[] fusion()", 50,
                     BODY + "pmt.local.TravelTimeSpray/pad"),
                    ("%pmt_conv1d.2 = f32[] custom-call()", 60,
                     BODY + "pmt.local.Conv1D/pmt_conv1d"),
                    ("%fusion.3 = f32[] fusion()", 20,
                     BODY + "concatenate"),
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
        == (CONFIG, "cgls_shots8", 1)
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["ns", "niter"]
    assert conf["file"] == "chipbench/configs/lsm_kirchhoff.json"
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= set(ACCEPTED_LISTS + NEW_METRICS)      # at least
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"][0] == CELL and m["layer"] == "Ops and kernels"
        assert m["moves"] == "latency_p50_ms"
        assert m["source"] == "device_trace"
    assert by_name["kirchhoff_roofline_pct"]["unit"] == "%"
    # six cells or more, one of four chips: the share allowed
    assert sum(w["chips"] == 4 for w in b["workloads"]) \
        <= max(1, len(b["workloads"]) // 2)


def test_the_entries_have_the_manifests_form():
    """Every free text of every entry 1 to 200 printable characters on
    one line (PR 34's first hand-in was refused for 203), every entry
    just the keys of its kind."""
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
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_time_under_the_spray_scope(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, stack(), {"iterations_per_solve": 2})
    # a sweep pair: the solve's time under the scope (two iterations
    # of 700 here) over its niter + 1 = 3 pairs, not over its iterations
    assert kirchhoff_device_ms.read(ctx) == pytest.approx(700 * 2 / 3 / 1e6)
    assert scope_time.under(ctx, "pmt.local.TravelTimeSpray") \
        == pytest.approx(700 / 1e6)
    assert scope_time.under(ctx, "pmt.local.Conv1D") \
        == pytest.approx(60 / 1e6)
    # the accepted reader's whole: those two and the stack's own op
    assert operator_device_ms.read(ctx) == pytest.approx(780 / 1e6)


def test_kirchhoff_roofline_is_the_floor_over_the_scopes_time(tmp_path,
                                                              monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, stack(), {"iterations_per_solve": 2})
    ctx["peaks"] = PEAK
    ctx["deployment"] = SimpleNamespace(
        dtype="float32", kirchhoff_cost=lambda: costs_lsm.kirchhoff(SIZES))
    floor_ms = 1e3 * (8 * 2048 * 524288 + 8 * 524288 + 8 * 2048 * 1024) \
        / 819e9
    assert kirchhoff_roofline_pct.read(ctx) == pytest.approx(
        100.0 * floor_ms / (700 * 2 / 3 / 1e6))
    assert any("bytes bind" in m for m in ctx["said"])
    ctx["deployment"] = SimpleNamespace(dtype="float32")  # no such operator
    assert kirchhoff_roofline_pct.read(ctx) is None


@pytest.mark.parametrize("planes", [stack(scoped=False), solved()],
                         ids=["unnamed-program", "no-such-scope"])
def test_a_program_without_the_scope_reads_nothing(tmp_path, monkeypatch,
                                                   planes):
    """What the parent gives: nothing, and no exception."""
    ctx = ctx_of(tmp_path, monkeypatch, planes, {"iterations_per_solve": 2})
    ctx["peaks"] = PEAK
    ctx["deployment"] = SimpleNamespace(
        dtype="float32", kirchhoff_cost=lambda: costs_lsm.kirchhoff(SIZES))
    untraced = {"trace": None, "cell": {"name": "x"}, "records": {},
                "peaks": PEAK, "deployment": ctx["deployment"],
                "log": print}
    for reader in (kirchhoff_device_ms, kirchhoff_roofline_pct):
        assert reader.read(ctx) is None
        assert reader.read(untraced) is None


def test_the_costs_are_the_issues_floor():
    """The tables ONCE an iteration at their stored 8 bytes, the image
    and the traces: 8.61 GB, 10.5 ms, bytes bind; 8 flops a pair-pixel,
    0.26 ms at the float32 peak."""
    k = costs_lsm.kirchhoff(SIZES)
    assert k["bytes"] == 8589934592 + 2 * 2097152 + 2 * 8388608
    assert k["flops"] == 8 * 1073741824
    f = costs.least_seconds(k, PEAK, "float32")
    assert f["binds"] == "bytes"
    assert 1e3 * f["seconds"] == pytest.approx(10.51, abs=0.01)
    assert 1e3 * f["flops_s"] == pytest.approx(0.26, abs=0.01)
    it = costs_lsm.iteration(SIZES)
    assert it["bytes"] == k["bytes"] + 4 * 8388608
    assert it["flops"] == k["flops"] + 4 * 81 * 2048 * 1024
    # two honest sweeps of the tables read under half of it
    two = 1e3 * (2 * 8589934592) / 819e9
    assert 45 < 100 * 1e3 * f["seconds"] / two < 52


def test_the_loops_comparison_refuses_bf16_products():
    """The control: a plain solve whose sprayed and gathered products
    are rounded to bfloat16 stands in for the program in the cell's own
    loop (tiny, on the CPU) and comes out as not correct by
    ``rel_tol`` — the warm-up stops, no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "chipbench", "scratch", "lsm_control.py"),
         "bf16", "--workload", CELL, "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode != 0
    assert "CONTROL" in proc.stderr
    assert "RuntimeError: warm-up: rel_tol" in proc.stderr, \
        proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("z0, reproducible", [(128.0, True), (0.0, False)],
                         ids=["image-below-the-surface", "image-in-it"])
def test_the_account_shows_where_float32_cgls_is_reproducible(z0,
                                                              reproducible):
    """``scratch/lsm_account.py`` (tiny, on the CPU, 8 shots): with the
    image one wavelength below the acquisition surface the plain
    reference and the SAME reference with its sums in another order
    agree to float32's noise after ten iterations, and the bfloat16
    control stands three orders above them; with the image's first row
    IN the surface the reordered twin itself drifts tenfold an
    iteration — the cause PERF.md section 6 names, not the program."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "chipbench", "scratch", "lsm_account.py"),
         "--rehearse", "--seeds", "1", "--niter", "10", "--sizes",
         json.dumps({"ns": 8, "z0": z0}), "--witnesses",
         "reordered,control,program", "--tag", "_test"],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads([ln for ln in proc.stdout.split("\n")
                      if ln.startswith("{")][-1])
    assert len(row["reordered"]) == len(row["control"]) == 10
    if reproducible:
        assert row["reordered"][-1] < 2e-6 and row["program"][-1] < 5e-6
        assert row["control"][-1] > 1e-4
    else:
        assert row["reordered"][4] < 2e-6          # five iterations hold
        assert row["reordered"][-1] > 1e-4         # ten do not
        assert row["reordered"][-1] > 30 * row["reordered"][-3]


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lsm_kirchhoff.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["ns", "niter"]
    assert set(cfg["reduced_why"]) == {"ns", "niter"}
    assert len(cfg["source"]) <= 200
    s = cfg["sizes"]
    assert (s["nz"], s["nx"], s["nr"], s["nt"], s["nwav"]) \
        == (512, 1024, 256, 1024, 81)
    assert s["ns_deployment"] == 32 and s["ns"] in (8, 4)
    assert (s["dt"], s["vel"], s["f0"], s["dz"], s["dx"]) \
        == (0.004, 2500.0, 20.0, 4.0, 4.0)
    assert {"survey", "geometry", "velocity", "family", "x0", "d"} \
        <= set(cfg["assumed"])
    g = cfg["guarantees"]
    assert g["niter"] in (10, 5) and 1 <= g["hold_niter"] < g["niter"]
    assert 1e-6 <= g["rel_tol"] < 1e-3      # under the control's reading
    assert 1 < g["resid_ratio"] < 1.7       # under a solve that stops early
    assert 0 <= g["repeat_tol"] <= 1e-6
    assert {"rel_tol_why", "resid_ratio_why", "repeat_tol_why",
            "hold_niter_why"} <= set(g)
    assert "bfloat16" in g["rel_tol_why"] and "ill-posed" in g["text"]
    assert "TO_BE_WRITTEN" not in json.dumps(cfg)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "cgls_shots8.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed_vstack" and mix["pool"] == 2
    assert mix["niter"] == g["niter"] and mix["callers"] == 1
    assert mix["hold_niter"] == g["hold_niter"]
    assert mix["trace"]["pre_s"] == 2.0 and mix["trace"]["slice_s"] > 0
    assert "TO_BE_WRITTEN" not in json.dumps(mix)


def test_the_cell_is_files_and_entries_only(tmp_path):
    """The benchmark as it was before PR 38 (this cell's entries taken
    out of the registry) plus the appended entries IS the benchmark
    now, as far as this cell goes: every list only GREW, in order, and
    holds AT LEAST what this PR added; the cell rehearses in a copy."""
    import shutil
    bench = os.path.join(ROOT, "chipbench")
    root = tmp_path / "checkout"
    shutil.copytree(bench, root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(ROOT, "pylops_mpi_tpu"),
               root / "pylops_mpi_tpu")
    now = registry()
    was = json.loads(json.dumps(now))
    was["configs"] = [c for c in was["configs"] if c["name"] != CONFIG]
    was["workloads"] = [w for w in was["workloads"] if w["name"] != CELL]
    was["per_layer"] = [m for m in was["per_layer"]
                        if m["name"] not in NEW_METRICS]
    for m in was["end_to_end"] + was["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].remove(CELL)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in now[key]]
        kept = [e["name"] for e in was[key]]
        # in order: what was there keeps its order, and what this PR
        # added comes after everything that was there BEFORE it
        assert [n for n in names if n in kept] == kept
    at = {key: [e["name"] for e in now[key]] for key in now
          if key in ("configs", "workloads", "per_layer")}
    assert at["configs"].index(CONFIG) >= 4
    assert at["workloads"].index(CELL) >= 5
    assert at["per_layer"].index(NEW_METRICS[0]) >= 31
    assert at["per_layer"].index(NEW_METRICS[1]) \
        == at["per_layer"].index(NEW_METRICS[0]) + 1
    for name in ACCEPTED_LISTS:
        m = {e["name"]: e for e in now["end_to_end"] + now["per_layer"]}[name]
        # appended after the four cells the list held before this PR
        assert m["workloads"].index(CELL) >= 4, name
    assert len(now["configs"]) >= len(was["configs"]) + 1
    assert len(now["workloads"]) >= len(was["workloads"]) + 1
    assert len(now["per_layer"]) >= len(was["per_layer"]) + 2
    assert (now["command"], now["paths"], now["run_seconds"]) \
        == (["python3", "chipbench/run.py"], ["chipbench"], 50)
    for rel in NEW_FILES:
        assert os.path.exists(os.path.join(bench, rel)), rel
    (root / "BENCHMARK.json").write_text(json.dumps(now))
    last = check_rehearsal(run_cell(str(root), CELL, 1), sources_of(now))
    assert last["metrics"]["compiles_in_window"]["value"] == 0
