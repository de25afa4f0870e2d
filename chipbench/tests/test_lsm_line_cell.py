"""Tests of the cell ``lsm_kirchhoff_line.cgls_shots32``: its registry
entries as named, the costs of one chip's share, the rehearsal on four
virtual devices, the loop's comparison refusing the bfloat16 control
there, the builder's refusal of a program whose ``MPIVStack`` does not
take its sharded form, and ``stack_reduce_ms`` on a synthesized trace
of two devices. No chip needed."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import costs, costs_lsm  # noqa: E402
from chipbench.builders import lsm_line  # noqa: E402
from chipbench.layers import stack_reduce_ms  # noqa: E402
from chipbench.tests.test_chipbench import (check_rehearsal,  # noqa: E402
                                            registry, run_cell, sources_of)
from chipbench.tests.test_lsm_cell import PEAK  # noqa: E402
from chipbench.tests.test_program_trace import ctx_of  # noqa: E402

CONFIG, CELL = "lsm_kirchhoff_line", "lsm_kirchhoff_line.cgls_shots32"
APPENDED = ["latency_p50_ms", "iter_device_ms", "loop_gap_pct",
            "iter_roofline_pct", "between_solves_ms", "operator_device_ms",
            "solver_self_device_ms", "solver_update_device_ms",
            "solver_cost_device_ms", "unscoped_device_ms", "launch_host_ms",
            "kirchhoff_device_ms", "kirchhoff_roofline_pct",
            "collective_ms_per_iter", "collective_exposed_pct"]


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_entries_are_the_ones_named():
    b = registry()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "cgls_shots32", 4)
    assert len(cell["why"]) <= 200
    conf = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert conf["reduced"] == ["niter"]
    assert conf["file"] == "chipbench/configs/lsm_kirchhoff_line.json"
    listing = {m["name"] for m in b["end_to_end"] + b["per_layer"]
               if CELL in m.get("workloads", [])}
    assert listing == set(APPENDED) | {"stack_reduce_ms"}
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in APPENDED:          # appended, last
            assert m["workloads"][-1] == CELL, m["name"]
    m = b["per_layer"][-1]
    assert m == {"name": "stack_reduce_ms", "unit": "ms", "better": "lower",
                 "source": "device_trace", "layer": "Collectives",
                 "moves": "latency_p50_ms", "workloads": [CELL]}
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 2
    assert b["configs"][-1]["name"] == CONFIG
    assert b["workloads"][-1]["name"] == CELL


def test_the_configuration_is_the_whole_line_of_the_one_chip_cell():
    line, share = _config(CONFIG), _config("lsm_kirchhoff")
    assert line["builder"] == "lsm_line" and line["reduced"] == ["niter"]
    assert line["layout"]["chips"] == 4
    want = {k: v for k, v in share["sizes"].items() if k != "ns_deployment"}
    want["ns"] = share["sizes"]["ns_deployment"]
    assert line["sizes"] == want
    s = line["sizes"]
    assert 8 * s["ns"] * s["nr"] * s["nz"] * s["nx"] == 34359738368
    assert line["source"] == share["source"]
    g = line["guarantees"]
    assert {"rel_tol_why", "hold_niter_why", "resid_ratio_why",
            "repeat_tol_why"} <= set(g)
    assert 1 <= g["hold_niter"] < g["niter"] == 10
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "cgls_shots32.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed_vstack" and mix["pool"] == 2
    assert (mix["niter"], mix["hold_niter"], mix["callers"]) \
        == (g["niter"], g["hold_niter"], 1)
    assert mix["trace"]["pre_s"] == 2.0 and mix["trace"]["slice_s"] >= 12


def test_the_per_chip_floor_is_the_one_chip_cell_s():
    """The costs the cell's roofline shares read are one chip's share's:
    ``lsm_kirchhoff``'s at ``ns`` 8, byte for byte and flop for flop."""
    share = lsm_line.one_chip(_config(CONFIG)["sizes"], 4)
    one = _config("lsm_kirchhoff")["sizes"]
    assert costs_lsm.kirchhoff(share) == costs_lsm.kirchhoff(one)
    assert costs_lsm.iteration(share) == costs_lsm.iteration(one)
    f = costs.least_seconds(costs_lsm.kirchhoff(share), PEAK, "float32")
    assert 1e3 * f["seconds"] == pytest.approx(10.51, abs=0.01)


def test_the_cell_rehearses_on_four_virtual_devices():
    b = registry()
    last = check_rehearsal(run_cell(ROOT, CELL, 0), sources_of(b))
    assert last["device"]["count"] == 4


def _env():
    return {k: v for k, v in os.environ.items()
            if not k.startswith("PYLOPS_MPI_TPU_") and k != "XLA_FLAGS"} \
        | {"JAX_PLATFORMS": "cpu"}


def test_the_loops_comparison_refuses_bf16_products():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "scratch",
                                      "lsm_line_control.py"),
         "bf16", "--workload", CELL, "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=_env())
    assert proc.returncode != 0
    assert "CONTROL" in proc.stderr
    assert "RuntimeError: warm-up: rel_tol" in proc.stderr, \
        proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout


def test_a_program_without_the_sharded_form_is_refused_at_once():
    """What the parent gives: its ``MPIVStack`` has no sharded form.
    Played by a program whose stacks never take it: the builder exits
    before anything of the line's size is made."""
    code = (
        "import sys; sys.argv[1:] = ['--workload', %r, '--seed', '3', "
        "'--seconds', '1', '--rehearse']\n"
        "from pylops_mpi_tpu.ops import stack\n"
        "stack.MPIVStack._shard = lambda self: 'mixed'\n"
        "from chipbench import run\n"
        "sys.exit(run.main())\n" % CELL)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=600,
                          env=_env())
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "MPIVStack form: replicated" in proc.stderr
    assert "lsm_kirchhoff_line cannot run on it" in proc.stderr
    assert "program:" not in proc.stderr        # no table was made
    assert '"correct"' not in proc.stdout


def _devices(spray, reduce_, scoped=True):
    """Two solves of one iteration on two devices whose shards differ:
    device ``d`` sprays for ``spray[d]`` and waits in the image's
    all-reduce ``reduce_[d]``; slice 0..10000."""
    body = "jit(f)/while/body/pmt.MPIVStack.rmatvec/shard_map/"
    planes = []
    for d, (sp, rd) in enumerate(zip(spray, reduce_)):
        ops = []
        for t in (0, 5000):
            at = t + 1300
            for name, dur, path in (
                    ("%pmt_kirchhoff_adj.1 = f32[] custom-call()", sp,
                     body + "pmt.local.TravelTimeSpray/pmt_kirchhoff_adj"),
                    ("%all-reduce.2 = f32[] all-reduce()", rd,
                     body + "pmt.collective.stack_reduce/psum"),
                    ("%fusion.5 = f32[] fusion()", 100,
                     "jit(f)/while/body/pmt.solver.step/add")):
                ops.append((name, at, dur, None,
                            {"tf_op": path} if scoped else None))
                at += dur
        planes.append((f"/device:TPU:{d}", [("XLA Ops", ops)]))
    host = [("cb.slice", 0, 10000), ("cb.solve", 1000, 4000),
            ("cb.solve", 6000, 3900),
            ("pmt.solver.cgls", 1100, 3890), ("pmt.solver.cgls", 6100, 3790)]
    return planes + [("/host:CPU", [("main", host)])]


def test_stack_reduce_is_the_mean_wait_a_sweep_pair(tmp_path, monkeypatch):
    """Device 0's shard sprays 900, device 1's 600: device 1 waits 300
    longer in the all-reduce. The reading is their mean over the
    ``niter + 1`` pairs; the log gives each device's two times."""
    ctx = ctx_of(tmp_path, monkeypatch, _devices((900, 600), (50, 350)),
                 {"iterations_per_solve": 1})
    assert stack_reduce_ms.read(ctx) == pytest.approx(
        (50 + 350) / 2 * 1 / 2 / 1e6)
    line = [s for s in ctx["said"] if s.startswith("stack_reduce_ms")]
    assert len(line) == 1
    assert "TPU:0 0.00045 / 2.5e-05" in line[0] \
        and "TPU:1 0.0003 / 0.000175" in line[0]


def test_stack_reduce_reads_nothing_without_the_scope(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch,
                 _devices((900, 600), (50, 350), scoped=False),
                 {"iterations_per_solve": 1})
    assert stack_reduce_ms.read(ctx) is None
    assert stack_reduce_ms.read({"trace": None, "cell": {"name": "x"},
                                 "records": {}, "log": print}) is None
