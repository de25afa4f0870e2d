"""The chip check and what it rests on (ISSUE 21).

``chip_smoke.py`` proves the system on the TPU; here its CPU rehearsal
(the same three stages, tiny, on the 8-virtual-device mesh) must pass
and must never claim the chip, the entry scripts must refuse to run
without a chip they were not asked to do without, no parent may hold a
backend over a child that needs one, and host-built operators must
land shard by shard on the devices that own them.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.ops.local import MatrixMult

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

SOLVES = ("two_sweep_f32", "normal_f32", "default_f32", "normal_bf16")
OPS = ("first_derivative_o3_edge", "first_derivative_o5_edge",
       "second_derivative", "laplacian_3d", "matrixmult_summa", "vstack",
       "fft2d", "fredholm1")


def _run(args, **env_changes):
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


# ------------------------------------------------------------ rehearsal
@pytest.fixture(scope="module")
def rehearsal():
    """One ``chip_smoke.py --rehearse`` run shared by the module."""
    p = _run(["chip_smoke.py", "--rehearse", "--seed", "3"])
    assert p.returncode == 0, p.stderr[-3000:]
    report, verdict = p.stdout.strip().splitlines()
    return dict(json.loads(report), verdict=json.loads(verdict))


def test_rehearsal_passes_and_never_claims_the_chip(rehearsal):
    assert rehearsal["rehearsal"] == "passed"
    assert rehearsal["chip"] == "not run"
    assert "ok" not in rehearsal
    assert rehearsal["platform"] == "cpu"
    assert rehearsal["device_kind"] == "cpu" and rehearsal["n_devices"] == 8
    assert rehearsal["seed"] == 3


def test_last_line_is_the_verdict_and_nothing_else(rehearsal):
    # the chip check reads the last stdout line and refuses any other
    # key: {"ok", "device": {"platform", "kind", "count"}} exactly
    verdict = rehearsal["verdict"]
    assert list(verdict) == ["ok", "device"]
    assert verdict["ok"] is False          # a rehearsal is not the chip
    assert verdict["device"] == {"platform": "cpu", "kind": "cpu",
                                 "count": 8}
    assert type(verdict["device"]["count"]) is int


def test_rehearsal_reports_its_environment(rehearsal):
    assert rehearsal["versions"]["jax"] == jax.__version__
    assert set(rehearsal["versions"]) == {"jax", "jaxlib", "libtpu"}
    cache = rehearsal["compile_cache"]
    assert cache["dir"] and cache["programs"] > 0
    assert cache["compiled"] == cache["programs"] - cache["hits"]
    assert rehearsal["native"]["staging"] in ("native", "numpy")
    assert rehearsal["fft_engine"] == "xla"
    assert "not measurements" in rehearsal["note"]


@pytest.mark.parametrize("name", SOLVES)
def test_rehearsal_flagship_solves_agree_with_reference(rehearsal, name):
    row = rehearsal["A"]["solves"][name]
    tol = chip_smoke.BF16_TOL if "bf16" in name else chip_smoke.F32_TOL
    assert 0.0 <= row["err"] <= tol
    assert row["err_true"] <= tol
    assert row["mosaic"] == 0   # the kernels are interpreted off the chip
    assert row["setup_s"] >= 0 and row["run_s"] > 0


def test_rehearsal_flagship_is_spread_over_the_mesh(rehearsal):
    a = rehearsal["A"]
    used = a["bytes_in_use"]
    assert len(used) == 8 and min(used) > 0 and max(used) == min(used)
    assert a["nblk"] == 8 * chip_smoke.TINY["blocks_per_chip"]
    assert a["ref_err_true"] <= chip_smoke.F32_TOL


def test_rehearsal_service_fills_one_full_and_one_ragged_bucket(rehearsal):
    b = rehearsal["B"]
    assert b["fills"] == [[3, 4], [16, 16]]
    assert b["requests"] == 19 and b["batches"] == 2
    assert b["err_max"] <= chip_smoke.F32_TOL


@pytest.mark.parametrize("name", OPS)
def test_rehearsal_roll_call(rehearsal, name):
    row, = [r for r in rehearsal["C"]["ops"] if r["name"] == name]
    errs = row["err"]
    assert {"matvec[auto]", "rmatvec[auto]"} <= set(errs)
    if name not in ("laplacian_3d", "fredholm1"):   # take overlap=
        assert {"matvec[off]", "rmatvec[off]", "auto_vs_off"} <= set(errs)
    assert max(errs.values()) <= chip_smoke.F32_TOL


def test_roll_call_is_the_list_the_dry_run_shares():
    cases = chip_smoke.roll_call(pmt.make_mesh(), chip_smoke.TINY)
    assert tuple(c["name"] for c in cases) == OPS
    with open(os.path.join(ROOT, "__graft_entry__.py")) as f:
        src = f.read()
    assert "chip_smoke.roll_call(" in src and "chip_smoke.run_case(" in src


# ----------------------------------------------- no chip, no fallback
def test_chip_smoke_without_a_chip_exits_nonzero_naming_the_platform():
    p = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert p.returncode == 2
    assert "platform 'cpu'" in p.stderr
    assert p.stdout.strip() == ""          # no result line


# ------------------------------------------------ one process per chip
MODULES = ("pylops_mpi_tpu", "pylops_mpi_tpu.resilience",
           "pylops_mpi_tpu.serving")


@pytest.fixture(scope="module")
def backends_after_import():
    """One fresh interpreter imports each module in turn and reports
    the backends JAX has initialised after each."""
    code = ("import importlib, json\n"
            "from jax._src import xla_bridge\n"
            "out = {}\n"
            f"for m in {MODULES!r}:\n"
            "    importlib.import_module(m)\n"
            "    out[m] = sorted(xla_bridge._backends)\n"
            "print(json.dumps(out))\n")
    p = _run(["-c", code])
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("module", MODULES)
def test_import_initialises_no_backend(backends_after_import, module):
    """The supervisor and ``serve_job`` parents import the package and
    then start workers that need the chip: the import must leave every
    backend untouched."""
    assert backends_after_import[module] == []


def test_entry_scripts_start_no_jax_children():
    """No entry script that initialises a backend starts a process."""
    spawners = ("subprocess", "multiprocessing", "os.fork", "Popen",
                "os.system")
    found = {}
    scripts = ["chip_smoke.py", "__graft_entry__.py"] + [
        os.path.join("benchmarks", f)
        for f in sorted(os.listdir(os.path.join(ROOT, "benchmarks")))
        if f.endswith(".py")]
    for rel in scripts:
        with open(os.path.join(ROOT, rel)) as f:
            src = f.read()
        hits = [s for s in spawners if s in src]
        if hits:
            found[rel] = hits
    assert found == {}


# ------------------------------------------- construction and placement
def _device_of(buf):
    dev, = buf.devices()
    return dev


def test_blockdiag_from_host_blocks_places_each_shard_on_its_device(rng):
    """8 GB per chip does not fit twice: host blocks go straight to
    the device that owns them — no per-block device copy is left
    alive and nothing is staged on device 0."""
    ndev = len(jax.devices())
    nblk, n = 2 * ndev, 24
    blocks = [rng.standard_normal((n, n)).astype(np.float32)
              for _ in range(nblk)]
    before = {id(a) for a in jax.live_arrays()}
    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32) for b in blocks])
    new = [a for a in jax.live_arrays() if id(a) not in before]
    stacked, = jax.tree_util.tree_leaves(Op)
    # one new device array — the stack; none of block shape
    assert [a.shape for a in new] == [(nblk, n, n)]
    assert all(isinstance(op.A_source, np.ndarray) for op in Op.ops)
    shards = sorted(stacked.addressable_shards, key=lambda s: s.index)
    assert len({s.device for s in shards}) == ndev
    for k, s in enumerate(shards):
        assert s.data.shape == (2, n, n)
        np.testing.assert_array_equal(np.asarray(s.data),
                                      np.stack(blocks[2 * k:2 * k + 2]))
    # the operator still applies, and a block used alone is placed then
    x = pmt.DistributedArray.to_dist(
        rng.standard_normal(nblk * n).astype(np.float32))
    want = np.concatenate([b @ x.asarray()[i * n:(i + 1) * n]
                           for i, b in enumerate(blocks)])
    np.testing.assert_allclose(Op.matvec(x).asarray(), want, rtol=2e-5,
                               atol=2e-5)
    assert isinstance(Op.ops[0].A, jax.Array)


def test_vstack_from_host_blocks_leaves_no_block_copies(rng):
    ndev = len(jax.devices())
    mats = [rng.standard_normal((4, 8)).astype(np.float32)
            for _ in range(ndev)]
    before = {id(a) for a in jax.live_arrays()}
    Op = pmt.MPIVStack([MatrixMult(m, dtype=np.float32) for m in mats])
    new = [a for a in jax.live_arrays() if id(a) not in before]
    assert [a.shape for a in new] == [(ndev, 4, 8)]
    assert len({s.device for s in new[0].addressable_shards}) == ndev


def test_stack_sharded_narrow_storage_is_cast_on_the_host(rng):
    from pylops_mpi_tpu.parallel.mesh import stack_sharded
    mesh = pmt.make_mesh()
    ndev = int(mesh.devices.size)
    mats = [rng.standard_normal((4, 4)) for _ in range(ndev)]  # f64 host
    A = stack_sharded(mats, mesh, jnp.bfloat16)
    assert A.dtype == jnp.bfloat16 and A.shape == (ndev, 4, 4)
    np.testing.assert_array_equal(
        np.asarray(A), np.stack(mats).astype(jnp.bfloat16))
    # device blocks take the stack-then-reshard route, same result
    B = stack_sharded([jnp.asarray(m) for m in mats], mesh, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(A), np.asarray(B))


def test_to_dist_host_array_goes_straight_to_its_shards(rng):
    ndev = len(jax.devices())
    x = rng.standard_normal(ndev * 6).astype(np.float32)
    before = {id(a) for a in jax.live_arrays()}
    d = pmt.DistributedArray.to_dist(x)
    new = [a for a in jax.live_arrays() if id(a) not in before]
    # the placed array only: no full-size staging copy on device 0
    assert [a.shape for a in new if a.size == x.size] == [x.shape]
    assert len({s.device for s in d._arr.addressable_shards}) == ndev
    np.testing.assert_array_equal(d.asarray(), x)


def test_matrixmult_keeps_a_host_matrix_on_the_host_until_used(rng):
    a = rng.standard_normal((5, 3))
    op = MatrixMult(a)
    assert op.A_source is a and op.shape == (5, 3)
    assert op.dtype == jax.dtypes.canonicalize_dtype(a.dtype)
    v = rng.standard_normal(3)
    np.testing.assert_allclose(op.matvec(v), a @ v, rtol=1e-6)
    assert isinstance(op.A_source, jax.Array)   # placed by the apply
    dev = MatrixMult(jnp.asarray(a))
    assert isinstance(dev.A_source, jax.Array)
    # first use under a trace still caches a concrete array
    traced = MatrixMult(a)
    np.testing.assert_allclose(jax.jit(traced.matvec)(v), a @ v, rtol=1e-6)
    assert not isinstance(traced.A_source, jax.core.Tracer)
    np.testing.assert_allclose(traced.rmatvec(a @ v), a.T @ (a @ v),
                               rtol=1e-6)


# --------------------------------------------------------- entry points
def test_entry_step_takes_the_operator_as_an_argument():
    """The flagship blocks are traced buffers of the jitted step, not
    constants baked into the program (a 512² f32 block printed as a
    constant is megabytes of text)."""
    import __graft_entry__ as ge
    try:
        fn, args = ge.entry()
    finally:
        pmt.set_default_mesh(None)   # entry() pins a 1-device mesh
    assert isinstance(args[0], pmt.MPIBlockDiag)
    text = jax.jit(fn).lower(*args).as_text()
    assert len(text) < 200_000
    assert "tensor<1x512x512xf32>" in text   # the blocks, as a parameter


@pytest.mark.parametrize("env,forced", [
    ({"JAX_PLATFORMS": None, "PYLOPS_MPI_TPU_PLATFORM": None}, False),
    ({"JAX_PLATFORMS": "cpu", "PYLOPS_MPI_TPU_PLATFORM": None}, True),
])
def test_examples_choose_the_cpu_only_when_asked(env, forced):
    code = ("import sys; sys.path.insert(0, 'examples')\n"
            "import _setup, jax, os\n"
            "print(repr(jax.config.jax_platforms), "
            "jax.config.jax_enable_x64, "
            "'device_count=8' in os.environ.get('XLA_FLAGS', ''))\n")
    p = _run(["-c", code], XLA_FLAGS=None, **env)
    assert p.returncode == 0, p.stderr[-2000:]
    platforms, x64, eight = p.stdout.split()
    if forced:
        assert (platforms, x64, eight) == ("'cpu'", "True", "True")
    else:
        assert platforms in ("None", "''") and x64 == "False" \
            and eight == "False"


def test_strip_provenance_ignores_where_a_program_came_from():
    from pylops_mpi_tpu.utils import hlo

    def first(x):
        return jnp.sin(x) * 2

    def second(x):
        return jnp.sin(x) * 2

    a = hlo.compiled_hlo(first, jnp.ones(4))
    b = hlo.compiled_hlo(second, jnp.ones(4))
    assert a != b
    assert hlo.strip_provenance(a) == hlo.strip_provenance(b)
    assert hlo.strip_provenance(a) != hlo.strip_provenance(
        hlo.compiled_hlo(lambda x: jnp.cos(x) * 2, jnp.ones(4)))
