"""Tests of what PR 32 added to the benchmark: the three layer readers
that split the stacked solve by the scope an op was lowered UNDER
(``scope_time``), on a synthesized trace; the cost functions; the
builder's plain operators against their adjoints; the loop's comparison
refusing the two deliberately wrong solves; the configuration's file.
The cell's rehearsal and the manifest lint are ``test_chipbench.py``'s,
which pick the cell up from the registry. No chip needed."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import costs, costs_poststack, scope_time  # noqa: E402
from chipbench.layers import (conv_roofline_pct,  # noqa: E402
                              modelling_device_ms, operator_device_ms,
                              regulariser_device_ms)
from chipbench.tests.test_program_trace import (DEV, ctx_of,  # noqa: E402
                                                solved)

BODY = "jit(f)/while/body/pmt.MPIStackedVStack.matvec/"


def stacked(scoped=True):
    """Two solves of two iterations of a stacked system; slice
    0..10000. An iteration: 400 under the convolution, 100 under the
    derivative, 50 of the modelling's own, 150 under the Laplacian, 30
    of the regulariser's scaling, 200 of the solver's own."""
    def op(name, at, dur, path):
        return (name, at, dur, None, {"tf_op": path} if scoped else None)

    ops = []
    for t in (0, 5000):
        ops.append(("%while.3 = () while()", t + 1200, 3700))
        at = t + 1300
        for i in range(2):
            for name, dur, path in (
                    ("%pmt_conv1d.1 = f32[] custom-call()", 400,
                     BODY + "pmt.MPIBlockDiag.matvec/pmt.local.Conv1D/"
                     "pmt_conv1d"),
                    ("%fusion.1 = f32[] fusion()", 100,
                     BODY + "pmt.MPIBlockDiag.matvec/"
                     "pmt.local.FirstDerivative/sub"),
                    ("%fusion.2 = f32[] fusion()", 50,
                     BODY + "pmt.MPIBlockDiag.matvec/mul"),
                    ("%fusion.3 = f32[] fusion()", 150,
                     BODY + "pmt._ScaledLinearOperator.matvec/"
                     "pmt.MPILaplacian.matvec/add"),
                    ("%fusion.4 = f32[] fusion()", 30,
                     BODY + "pmt._ScaledLinearOperator.matvec/mul"),
                    ("%fusion.5 = f32[] fusion()", 200,
                     "jit(f)/while/body/add")):
                ops.append(op(name, at, dur, path))
                at += dur
    host = [("cb.slice", 0, 10000), ("cb.solve", 1000, 4000),
            ("cb.solve", 6000, 3900),
            ("pmt.solver.cgls", 1100, 3890), ("pmt.solver.cgls", 6100, 3790)]
    return [(DEV, [("XLA Ops", ops)]), ("/host:CPU", [("main", host)])]


PEAK = {"bf16_flops_per_s": 197e12, "f32_passes": 6,
        "hbm_bytes_per_s": 819e9}
SIZES = {"ny": 192, "nx": 1024, "nt0": 1024}


def test_time_under_a_scope_anywhere_in_the_path(tmp_path, monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, stacked(),
                 {"iterations_per_solve": 2})
    assert modelling_device_ms.read(ctx) == pytest.approx(550 / 1e6)
    assert regulariser_device_ms.read(ctx) == pytest.approx(150 / 1e6)
    assert scope_time.under(ctx, "pmt.local.Conv1D") \
        == pytest.approx(400 / 1e6)
    # the accepted reader's whole: those two and the stack's own ops
    assert operator_device_ms.read(ctx) == pytest.approx(
        (550 + 150 + 30) / 1e6)
    assert scope_time.under(ctx, "pmt.NoSuchOperator.") is None


def test_conv_roofline_is_the_floor_over_the_scopes_time(tmp_path,
                                                         monkeypatch):
    ctx = ctx_of(tmp_path, monkeypatch, stacked(),
                 {"iterations_per_solve": 2})
    ctx["peaks"] = PEAK
    ctx["deployment"] = SimpleNamespace(
        dtype="float32",
        conv_cost=lambda: costs_poststack.convolution(SIZES, 41))
    floor_ms = 1e3 * (4 * 4 * 192 * 1024 * 1024) / 819e9      # bytes bind
    assert conv_roofline_pct.read(ctx) == pytest.approx(
        100.0 * floor_ms / (400 / 1e6))
    assert any("bytes bind" in m for m in ctx["said"])
    ctx["deployment"] = SimpleNamespace(dtype="float32")   # no convolution
    assert conv_roofline_pct.read(ctx) is None


@pytest.mark.parametrize("planes", [stacked(scoped=False), solved()],
                         ids=["unnamed-program", "no-such-scopes"])
def test_a_program_without_the_scopes_reads_nothing(tmp_path, monkeypatch,
                                                    planes):
    """What the parent gives: nothing, and no exception."""
    ctx = ctx_of(tmp_path, monkeypatch, planes, {"iterations_per_solve": 2})
    ctx["peaks"] = PEAK
    ctx["deployment"] = SimpleNamespace(
        dtype="float32",
        conv_cost=lambda: costs_poststack.convolution(SIZES, 41))
    assert regulariser_device_ms.read(ctx) is None
    assert conv_roofline_pct.read(ctx) is None
    if planes[0][1][0][1][1][4] is None:        # the unnamed program
        assert modelling_device_ms.read(ctx) is None
    untraced = {"trace": None, "cell": {"name": "x"}, "records": {},
                "peaks": PEAK, "deployment": ctx["deployment"],
                "log": print}
    for reader in (modelling_device_ms, regulariser_device_ms,
                   conv_roofline_pct):
        assert reader.read(untraced) is None


@pytest.mark.parametrize("taps,flops_ms", [(41, 1.19), (81, 2.17)])
def test_the_costs_are_the_issues_floor(taps, flops_ms):
    """Six volume streams whatever the wavelet; the flops follow its
    taps (ISSUE 32 counted 81; the configuration runs upstream's 41)."""
    it = costs_poststack.iteration(SIZES, taps)
    V = 192 * 1024 * 1024
    assert it["bytes"] == 6 * 4 * V == 4831838208          # 4.83 GB
    assert it["flops"] == (4 * taps + 30) * V
    floor = costs.least_seconds(it, PEAK, "float32")
    assert floor["binds"] == "bytes"
    assert 1e3 * floor["seconds"] == pytest.approx(5.9, abs=0.01)
    assert 1e3 * floor["flops_s"] == pytest.approx(flops_ms, abs=0.01)
    conv = costs.least_seconds(costs_poststack.convolution(SIZES, taps),
                               PEAK, "float32")
    assert conv["binds"] == "bytes"
    assert 1e3 * conv["seconds"] == pytest.approx(3.93, abs=0.01)


def test_the_plain_operators_and_their_adjoints():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from chipbench.builders import poststack as B
    rng = np.random.default_rng(0)
    wav = B.ricker(21, 0.004, 15.0)
    assert len(wav) == 41 and np.argmax(wav) == 20
    v = rng.standard_normal((3, 4, 150)).astype(np.float32)
    got = np.asarray(B.conv_t(jnp.asarray(v), wav, 20))
    want = np.apply_along_axis(
        lambda t: np.convolve(t, wav.astype(np.float64))[20:170], -1,
        v.astype(np.float64))
    assert np.linalg.norm(got - want) <= 1e-6 * np.linalg.norm(want)
    mv, rmv = B.plain_system(wav, 10.0)
    u = tuple(jnp.asarray(rng.standard_normal(v.shape).astype(np.float32))
              for _ in range(2))
    y = mv(jnp.asarray(v))
    lhs = sum(float(jnp.vdot(a, b)) for a, b in zip(u, y))
    rhs = float(jnp.vdot(rmv(u), jnp.asarray(v)))
    assert abs(lhs - rhs) <= 1e-4 * abs(lhs)
    # the plain solve: the answer is the start plus the correction, and
    # the correction form's data is the start's residual
    x0 = jnp.asarray(v)
    d = mv(x0 + 0.1)[0]
    x, dx, r0, r1, drop = B.plain_solve(wav, 10.0, 8)(d, None, x0)
    assert float(drop) < 1.0
    assert float(jnp.linalg.norm(x - (x0 + dx))) == 0.0
    a0 = mv(x0)
    assert float(jnp.linalg.norm(r0 - (d - a0[0]))) \
        <= 1e-6 * float(jnp.linalg.norm(r0))
    # CGLS from x0 IS CGLS from zero on that residual
    again = B.plain_solve(wav, 10.0, 8)(r0, r1, jnp.zeros_like(x0))[0]
    assert float(jnp.linalg.norm(again - dx)) \
        <= 1e-5 * float(jnp.linalg.norm(dx))


@pytest.mark.parametrize("kind", ["bf16", "taps31"])
def test_the_loops_comparison_refuses_a_wrong_convolution(kind):
    """The control: a plain solve whose convolution rounds its products
    to bfloat16, or keeps 31 taps, stands in for the program in the
    cell's own loop (tiny, on the CPU) and comes out as not correct by
    ``corr_tol`` — set-up stops, no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "chipbench", "scratch", "poststack_control.py"),
         kind, "--workload", "poststack_3d.reg_cgls", "--seed", "3",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode != 0
    assert "CONTROL" in proc.stderr
    assert "RuntimeError: set-up: corr_tol" in proc.stderr, proc.stderr[-2000:]
    assert '"correct"' not in proc.stdout


def test_judge_is_the_whole_comparison():
    from chipbench.loops.closed_stacked import judge
    limits = {"rel_tol": 3e-6, "corr_tol": 1e-4, "resid_drop": 0.5}
    assert judge({"rel_tol": 2e-7, "corr_tol": 1e-6, "resid_drop": 0.3},
                 limits) == []
    assert judge({"rel_tol": 2e-7, "corr_tol": 5e-4}, limits) == ["corr_tol"]
    assert judge({"rel_tol": float("nan")}, limits) == ["rel_tol"]
    assert judge({"resid_drop": 0.5}, limits) == []


def test_the_configuration_states_what_the_issue_asks():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "poststack_3d.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["ny", "niter"]
    assert set(cfg["reduced_why"]) == {"ny", "niter"}
    s = cfg["sizes"]
    assert (s["ny_deployment"], s["nx"], s["nt0"]) == (768, 1024, 1024)
    assert 2 * s["ntwav_half"] - 1 == 41 and s["epsR"] == 100.0
    assert {"survey", "wavelet", "epsR", "damp", "family", "x0", "d"} \
        <= set(cfg["assumed"])
    g = cfg["guarantees"]
    assert g["rel_tol"] <= 1e-3 and g["niter"] == 30
    assert g["rel_tol"] < g["corr_tol"] <= 1e-3
    assert {"rel_tol_why", "corr_tol_why", "resid_drop_why"} <= set(g)
    assert "true model" in g["text"] and "ill-posed" in g["text"]
    assert "PLACEHOLDER" not in json.dumps(cfg)
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "reg_cgls.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed_stacked" and mix["pool"] == 2
    assert mix["niter"] == 30 and mix["trace"]["pre_s"] == 2.0
    assert "PLACEHOLDER" not in json.dumps(mix)
