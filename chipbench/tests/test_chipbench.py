"""Tests of the benchmark's own yardstick. Run with

    python -m pytest chipbench/tests

They need no chip: the trace reduction is checked on a synthetic trace
and on a small trace recorded on the chip (``fixture_k1.xplane.pb``,
cut from a run of ``flagship_n4096.solve_k1`` with
``xplane_writer.trim``); every cell is rehearsed tiny on the CPU.
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from chipbench import costs, stats, trace_reduce as T  # noqa: E402
from chipbench.tests.xplane_writer import xspace  # noqa: E402

DEV = "/device:TPU:0"


def registry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ trace reduce
def synthetic(tmp_path):
    """One device: a ``while`` holding two fusions and a synchronous
    all-reduce, an asynchronous all-gather pair half hidden behind a
    fusion, and a copy between two solves; host spans around them."""
    ops = [("while.1", 1000, 9000),
           ("fusion.1", 1100, 400),          # 1100-1500
           ("all-reduce.2", 1600, 300),      # 1600-1900 exposed
           ("all-gather-start.3", 2000, 100),   # in flight 2000-3200
           ("fusion.4", 2100, 600),          # 2100-2700 hides part
           ("all-gather-done.3", 3000, 200),
           ("copy.5", 11000, 500),           # second solve: 11000-11500
           ]
    host = [("cb.slice", 500, 12000), ("cb.solve", 800, 9500),
            ("cb.check", 10350, 300), ("cb.solve", 10700, 1000),
            ("python_noise", 0, 10)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace([
        (DEV, [("XLA Ops", ops), ("XLA Modules", [("jit_f", 900, 9200)])]),
        ("/host:CPU", [("main", host)])]))
    return T.load(str(path))


def test_intervals():
    assert T.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert T.total([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert T.complement([(2, 3)], 0, 4) == [(0, 2), (3, 4)]
    assert T.clip([(0, 5), (8, 9)], 2, 8) == [(2, 5)]


def test_leaves_drop_containers():
    ev = [(0, 100, "while"), (10, 20, "a"), (30, 90, "cond"), (40, 50, "b")]
    assert [n for _, _, n in T.leaves(ev)] == ["a", "b"]


def test_busy_union_and_idle_share(tmp_path):
    t = synthetic(tmp_path)
    assert (t.lo, t.hi) == (500.0, 12500.0)
    # leaves only: 400 + 300 + 100 + 600 + 200 + 500
    assert T.total(t.busy(DEV)) == 2100
    assert t.busy_s() == pytest.approx(2100e-9)
    assert t.idle_share() == pytest.approx(1 - 2100 / 12000)
    assert t.window_s == pytest.approx(12000e-9)
    assert "XLA Modules" not in str(t.devices)


def test_collective_time_and_exposed_part(tmp_path):
    t = synthetic(tmp_path)
    fly, exposed = t.collective_exposed(DEV)
    # all-reduce 300 + all-gather pair 2000..3200 = 1500 in flight;
    # fusion.4 hides 600 of it
    assert fly == 1500
    assert exposed == 900
    s, e = t.span_list("solve")[0]
    assert t.collective_exposed(DEV, s, e) == (1500, 900)


def test_per_span_and_gap_attribution(tmp_path):
    t = synthetic(tmp_path)
    first, second = t.per_span("solve", DEV)
    assert first == (1100.0, 3200.0, 1600.0)
    assert second == (11000.0, 11500.0, 500.0)
    gaps = t.gaps(DEV, top=3)
    # longest: 3200 -> 11000, its middle (7100) lies in the first solve
    assert gaps[0] == ("solve", pytest.approx(7800e-9))
    names = [n for n, _ in t.gaps(DEV, top=20)]
    assert "outside spans" in names        # 500 -> 1100 starts before
    assert t.top_ops(2)[0] == ("fusion.4", pytest.approx(600e-9))


FIXTURE = os.path.join(HERE, "fixture_k1.xplane.pb")


def test_recorded_trace():
    """The trace cut from a chip run of ``flagship_n4096.solve_k1``
    (three solves of thirty iterations on one v5e chip): the TPU plane
    is found, the solver's ``while`` is not counted as work, the
    benchmark's spans are read, and the layer readers give what the
    full run gave."""
    t = T.load(FIXTURE)
    assert list(t.devices) == [DEV]
    assert not any(n.startswith("while") for _, _, n in t.devices[DEV])
    assert all(" = " not in n for _, _, n in t.devices[DEV])
    assert len(t.span_list("solve")) == 3 and len(t.span_list("check")) == 2
    assert 0 < t.idle_share() < 0.02
    assert t.busy_s() < t.window_s
    assert t.top_ops(1)[0][0].startswith("multiply_reduce_fusion")
    assert all(name == "solve" for name, _ in t.gaps(DEV, 3))
    assert t.collective_exposed(DEV) == (0.0, 0.0)     # one chip

    from chipbench.layers import (between_solves_ms, collective_ms_per_iter,
                                  iter_device_ms, iter_roofline_pct,
                                  loop_gap_pct)

    class Dep:
        dtype = "float32"
        cost = staticmethod(lambda k=1: costs.blockdiag(
            {"n": 4096, "blocks_per_chip": 128}, k))

    ctx = {"trace": t, "records": {"iterations_per_solve": 30, "columns": 1},
           "peaks": costs.peaks("TPU v5 lite"), "deployment": Dep,
           "log": lambda m: None}
    assert iter_device_ms.read(ctx) == pytest.approx(24.0, abs=0.3)
    assert iter_roofline_pct.read(ctx) == pytest.approx(43.7, abs=0.6)
    assert 0 < loop_gap_pct.read(ctx) < 2
    assert 0.5 < between_solves_ms.read(ctx) < 3
    assert collective_ms_per_iter.read(ctx) is None    # nothing to read
    assert iter_device_ms.read(dict(ctx, trace=None)) is None


def test_async_line_counts_as_in_flight_not_busy(tmp_path):
    path = tmp_path / "a.xplane.pb"
    path.write_bytes(xspace([(DEV, [
        ("XLA Ops", [("%fusion.1 = f32[8] fusion(f32[8] %all-gather.9), "
                      "kind=kLoop", 0, 100),
                     ("%fusion.2 = f32[8] fusion(%p)", 300, 100),
                     ("%psum.17 = f32[] all-reduce(f32[] %x), channel_id=1",
                      500, 50)]),
        ("Async XLA Ops", [("%all-gather-start.9 = (f32[8]) all-gather-start(%x)", 50, 300)]),
    ])]))
    t = T.load(str(path))
    # the fusion that consumes %all-gather.9 is not a collective
    # and jax.lax.psum's all-reduce is one, whatever its name
    assert [n for _, _, n in t.devices[DEV]] == [
        "fusion.1", "fusion.2", "psum.17 all-reduce"]
    assert T.total(t.busy(DEV)) == 250
    assert t.collective_exposed(DEV) == (350.0, 250.0)


# ------------------------------------------------------------------- stats
def test_percentile_needs_ten_beyond():
    s = list(range(1, 201))                    # 200 samples
    assert stats.percentile(s, 95.0) == 190    # exactly ten beyond
    assert stats.percentile(s[:199], 95.0) is None
    assert stats.percentile(s, 99.0) is None
    assert stats.percentile([], 50.0) is None
    # a missing answer is infinitely late and can only lengthen the tail
    assert stats.percentile(s[:-11] + [math.inf] * 11, 95.0) == math.inf
    assert stats.median([3, 1, 2, 10]) == 2.5


def test_open_loop_clock_runs_from_due_time():
    due = [0.0, 1.0, 2.0]
    answered = [0.5, 3.0, None]
    lat = stats.open_loop_latencies(due, answered, [True, True, True])
    assert lat == [0.5, 2.0, math.inf]         # not from the send time
    assert stats.open_loop_latencies(due, answered,
                                     [True, False, True])[1] == math.inf
    assert stats.lateness(due, [0.0, 1.25, 1.9]) == [0.0, 0.25, 0.0]


def test_arrivals_fixed_work_from_the_seed():
    a, b = stats.arrivals(16.0, 20.0, 1), stats.arrivals(16.0, 20.0, 2)
    assert len(a) == len(b) == 320
    assert list(a) == sorted(a) and 0 <= a[0] and a[-1] <= 20.0
    assert list(a) != list(b)
    assert list(a) == list(stats.arrivals(16.0, 20.0, 1))


# ------------------------------------------------------------------- costs
def test_unknown_device_kind_is_an_error():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        costs.peaks("_source")


def test_roofline_floor_names_its_bound():
    peak = costs.peaks("TPU v5 lite")
    c = costs.blockdiag({"n": 4096, "blocks_per_chip": 128})
    f = costs.least_seconds(c, peak)
    assert f["binds"] == "bytes"
    assert f["seconds"] == pytest.approx(8589934592 / 819e9, rel=1e-3)
    s = costs.summa({"N": 16384, "K": 16384, "M": 64, "grid": [2, 2]})
    assert costs.least_seconds(s, peak)["binds"] == "flops"


# ---------------------------------------------------------------- manifest
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_lint():
    b = registry()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    confs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    assert "setup_s" in e2e
    def where(m):
        return set(m.get("workloads", cells))

    for m in b["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
        # reported only where the metric it moves is
        assert where(m) <= where(e2e[m["moves"]]), m["name"]
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           m["name"] + ".py"))
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert c["file"].startswith("chipbench/")
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(BENCH, "builders",
                                           cfg["builder"] + ".py"))
        assert "rehearse" in cfg and "guarantees" in cfg
    four = 0
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        mix = json.load(open(os.path.join(BENCH, "traffic",
                                          w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "loops",
                                           mix["loop"] + ".py"))
        if "rate_per_s" in mix:        # an open loop fixes its instants
            assert isinstance(mix["arrival_seed"], int)
        four += w["chips"] == 4
        assert any(w["name"] in where(m) for m in b["per_layer"]), \
            f"{w['name']} reports no per-layer metric"
        assert any(w["name"] in where(m) for m in b["end_to_end"]
                   if m["name"] != "setup_s"), \
            f"{w['name']} reports no end-to-end metric besides setup_s"
    assert four <= max(1, len(b["workloads"]) // 2)
    assert {c["name"] for c in b["configs"]} == \
        {w["config"] for w in b["workloads"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


# --------------------------------------------------------------- rehearsal
def run_cell(root, workload, trace, seconds="1"):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYLOPS_MPI_TPU_") and k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", workload, "--seed", "3", "--seconds", seconds,
         "--trace", str(trace), "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env)


def check_rehearsal(proc, sources):
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    # a CPU run names no device metric: counts only
    for name in last["metrics"]:
        assert sources[name] == "program_counter", name
    return last


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload",
                         [w["name"] for w in registry()["workloads"]])
def test_rehearse_every_cell(workload, trace):
    b = registry()
    sources = {m["name"]: m["source"]
               for m in b["end_to_end"] + b["per_layer"]}
    last = check_rehearsal(run_cell(ROOT, workload, trace), sources)
    chips = {w["name"]: w["chips"] for w in b["workloads"]}[workload]
    assert last["device"]["count"] == chips
    if trace:
        assert last["metrics"]["compiles_in_window"]["value"] == 0


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         registry()["workloads"][0]["name"], "--seed", "0", "--seconds",
         "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "cpu" in proc.stderr


def checkout_copy(tmp_path):
    """A copy of the benchmark to add to, and its files as they were."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "*.pb"))
    os.symlink(os.path.join(ROOT, "pylops_mpi_tpu"),
               root / "pylops_mpi_tpu")
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    return root, before


def sources_of(b):
    return {m["name"]: m["source"] for m in b["end_to_end"] + b["per_layer"]}


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    """A throw-away configuration, traffic mix, two cells and a
    per-layer metric are ADDED (files, registry entries, and the cells'
    names appended to the ``workloads`` of metrics that are there) to a
    copy of the benchmark; no file that was there is edited, and the
    new cells run in rehearsal."""
    root, before = checkout_copy(tmp_path)
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "flagship_n4096.json")))
    cfg.update(name="throwaway_n64", sizes={"n": 64, "blocks_per_chip": 2},
               rehearse={"n": 16, "blocks_per_chip": 2})
    (root / "chipbench/configs/throwaway_n64.json").write_text(
        json.dumps(cfg))
    (root / "chipbench/traffic/solve_quick.json").write_text(json.dumps(
        {"name": "solve_quick", "loop": "closed_solve", "callers": 1,
         "pool": 3, "niter": 30, "rehearse": {}}))
    (root / "chipbench/layers/solves_counted.py").write_text(
        "def read(ctx):\n    return ctx['records']['attempted']\n")
    b = registry()
    quick, served = "throwaway_n64.solve_quick", "throwaway_n64.serve_open"
    b["configs"].append({"name": "throwaway_n64", "source": cfg["source"],
                         "file": "chipbench/configs/throwaway_n64.json",
                         "reduced": cfg["reduced"], "why": "test"})
    b["workloads"] += [
        {"name": quick, "config": "throwaway_n64", "traffic": "solve_quick",
         "chips": 1, "why": "test"},
        {"name": served, "config": "throwaway_n64", "traffic": "serve_open",
         "chips": 1, "why": "test: a mix that is there, on a new config"}]
    b["per_layer"].append({"name": "solves_counted", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "Solvers", "moves": "latency_p50_ms",
                           "workloads": [quick]})
    for m in b["end_to_end"] + b["per_layer"]:
        if "flagship_n4096.solve_k1" in m.get("workloads", []):
            m["workloads"].append(quick)
        if "flagship_n4096.serve_open" in m.get("workloads", []):
            m["workloads"].append(served)
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    last = check_rehearsal(run_cell(str(root), quick, 1), sources_of(b))
    assert last["metrics"]["solves_counted"]["value"] == last["attempted"]
    # a metric that was there covers the new cell once its name is
    # appended to the metric's list
    last = check_rehearsal(run_cell(str(root), served, 1), sources_of(b))
    assert 0 < last["metrics"]["useful_cols_pct"]["value"] <= 100
    assert "solves_counted" not in last["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "a file that was there was edited"


SUMMA = {
    "name": "throwaway_summa", "builder": "summa",
    "source": "upstream examples/plot_summamatrixmult.py, tiny",
    "sizes": {"N": 256, "K": 256, "M": 8, "grid": [2, 2]},
    "reduced": ["niter"], "guarantees": {"rel_tol": 1e-4},
    "rehearse": {"N": 64, "K": 64, "M": 8}}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_four_chip_pieces_wait_for_their_cell(tmp_path, trace):
    """No four-chip cell is registered (PERF.md section 4), but what
    one needs is here and works: the ``summa`` builder with its plain
    reference, the ``cgls_m64`` mix and the collective readers. A
    configuration, a cell and the two collective entries, added as
    data to a copy, rehearse on four virtual devices."""
    root, before = checkout_copy(tmp_path)
    (root / "chipbench/configs/throwaway_summa.json").write_text(
        json.dumps(SUMMA))
    b = registry()
    cell = "throwaway_summa.cgls_m64"
    b["configs"].append({"name": "throwaway_summa",
                         "source": SUMMA["source"],
                         "file": "chipbench/configs/throwaway_summa.json",
                         "reduced": ["niter"], "why": "test"})
    b["workloads"].append({"name": cell, "config": "throwaway_summa",
                           "traffic": "cgls_m64", "chips": 4, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "flagship_n4096.solve_k1" in m.get("workloads", []):
            m["workloads"].append(cell)
    for name, unit in (("collective_ms_per_iter", "ms"),
                       ("collective_exposed_pct", "%")):
        b["per_layer"].append({"name": name, "unit": unit,
                               "better": "lower", "source": "device_trace",
                               "layer": "Collectives",
                               "moves": "latency_p50_ms",
                               "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    last = check_rehearsal(run_cell(str(root), cell, trace), sources_of(b))
    assert last["device"]["count"] == 4
    assert {p: p.read_bytes() for p in before} == before


def test_the_stored_operator_is_held_to_the_generated_blocks():
    """The flagship's reference runs on the operator's own array, so
    the builder refuses an array that is not float32 or differs from
    the seed's blocks in a single bit of a sampled row."""
    import jax.numpy as jnp
    import numpy as np
    from chipbench.builders.blockdiag import held_as_generated, make_blocks
    blocks = make_blocks(4, 16, seed=5)
    assert list(blocks[0, :2, 0]) == list(make_blocks(4, 16, 5)[0, :2, 0])
    held_as_generated(jnp.asarray(blocks), blocks, 5)
    with pytest.raises(RuntimeError, match="bfloat16"):
        held_as_generated(jnp.asarray(blocks, jnp.bfloat16), blocks, 5)
    with pytest.raises(RuntimeError, match="reference cannot use it"):
        held_as_generated(jnp.asarray(blocks[:, :8]), blocks, 5)
    off = np.nextafter(blocks, np.float32(np.inf))   # one ulp, everywhere
    with pytest.raises(RuntimeError, match="bit for bit"):
        held_as_generated(jnp.asarray(off), blocks, 5)
