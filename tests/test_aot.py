"""AOT executable bank (round 18): cold-start elimination.

The three hard pins:

- ``PYLOPS_MPI_TPU_AOT=off`` (and unset) is a NO-OP: ``_get_fused``
  takes the exact pre-AOT jit path (``maybe_aot_fused`` returns None),
  the seam performs zero compiles and emits zero ``aot.*`` events —
  the same exact-equality discipline as the tune/guards/CA off pins.
- A bank seeded once replays in a FRESH process with ZERO fresh XLA
  compiles (``aot.compile_count()``) and bit-identical answers vs
  ``AOT=off``.
- Every corruption/mismatch mode — unreadable index, schema drift,
  truncated payload, foreign jax version/chip, stale avals, a wrong
  executable under a valid index row — is a CLASSIFIED miss
  (``aot.cache_error``) that falls back to a fresh compile: never a
  crash, never a stale answer.
"""

import json
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIBlockDiag, aot, cg
from pylops_mpi_tpu.aot import store as astore
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.ops.local import MatrixMult

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _aot_isolation(monkeypatch):
    """Every test starts with the AOT tier off, no bank dir, an empty
    memory tier / fused cache / warmed-signature set, and a clean
    trace buffer (the CI ``test-aot`` leg arms the knobs globally;
    this suite manages its own arms, the ``test_ca.py`` pattern)."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_AOT", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_AOT_CACHE", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_COMPILE_CACHE", raising=False)
    # the tier-1 command and every CI leg arm jax's persistent
    # compilation cache at package import; disarm it for this suite —
    # an XLA-cache-hit compile serializes into a payload that does not
    # round-trip on the CPU backend, which would turn the exact
    # compile-count pins below into (correct, classified) fallback
    # churn. The round-trip fence itself is pinned by
    # test_unroundtrippable_payload_not_banked.
    import jax
    prev_cc_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    # spans mode records the aot.* decision events this suite asserts
    # on WITHOUT arming in-loop telemetry (which would retrace the
    # fused programs under a different cache key — telemetry is a
    # full-mode feature, pinned by test_diagnostics.py)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")

    def _reset():
        aot.clear_memory()
        aot.reset_compile_count()
        pmt.clear_fused_cache()
        from pylops_mpi_tpu.serving import engine
        engine.clear_warmed_signatures()
        trace.clear_events()

    _reset()
    yield
    jax.config.update("jax_compilation_cache_dir", prev_cc_dir)
    _reset()


def _events(name):
    return [e for e in trace.get_events() if e.get("name") == name]


def _mats(nblk=4, nb=6, seed=7):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(nblk):
        a = rng.standard_normal((nb, nb)).astype(np.float32)
        out.append((a @ a.T / nb
                    + 2.0 * np.eye(nb, dtype=np.float32))
                   .astype(np.float32))
    return out


def _op(mats):
    return MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])


def _solve(Op, n, niter=6, seed=3):
    rng = np.random.default_rng(seed)
    y = DistributedArray(global_shape=n, dtype=np.float32)
    y[:] = rng.standard_normal(n).astype(np.float32)
    x = cg(Op, y, niter=niter, tol=0.0, fused=True)[0]
    return np.asarray(x.asarray())


# ------------------------------------------------------------ mode seam
def test_aot_mode_resolution(monkeypatch):
    assert astore.aot_mode() == "off"
    for raw, want in (("on", "on"), ("ON ", "on"), ("auto", "auto"),
                      ("1", "on"), ("0", "off"), ("", "off")):
        monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", raw)
        assert astore.aot_mode() == want
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "banana")
    with pytest.warns(UserWarning, match="PYLOPS_MPI_TPU_AOT"):
        assert astore.aot_mode() == "off"


def test_auto_arms_only_with_bank_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "auto")
    assert not astore.aot_enabled()
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT_CACHE", str(tmp_path))
    assert astore.aot_enabled()
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "off")
    assert not astore.aot_enabled()


def test_off_seam_untouched():
    """The off pin: with AOT unset the seam is never consulted — the
    plain jit path runs, zero AOT compiles are counted, zero ``aot.*``
    events fire, and ``maybe_aot_fused`` short-circuits to None."""
    import jax
    assert aot.maybe_aot_fused(
        jax.jit(lambda op, v: v), object(), ("k",)) is None
    mats = _mats()
    x = _solve(_op(mats), 24)
    assert np.all(np.isfinite(x))
    assert aot.compile_count() == 0
    assert [e for e in trace.get_events()
            if str(e.get("name", "")).startswith("aot.")] == []


def test_on_vs_off_bit_identical_memory_only(monkeypatch):
    """AOT=on with no bank dir (memory-only): the flat-call replay of
    the explicitly-compiled executable returns the EXACT bytes the
    plain jit path returns — same lowered program, different executor."""
    mats = _mats()
    x_off = _solve(_op(mats), 24)
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    pmt.clear_fused_cache()
    aot.clear_memory()
    x_on = _solve(_op(mats), 24)
    assert aot.compile_count() == 1
    np.testing.assert_array_equal(x_on, x_off)


def test_new_instance_same_signature_hits_memory(monkeypatch):
    """The structural bank key: a SECOND operator instance carrying
    the same matrices replays the first instance's executable from the
    memory tier — zero additional compiles (the restarted-daemon
    scenario the id-keyed fused cache alone cannot serve)."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    mats = _mats()
    x1 = _solve(_op(mats), 24)
    assert aot.compile_count() == 1
    x2 = _solve(_op(mats), 24)   # fresh instance, same signature
    assert aot.compile_count() == 1
    assert _events("aot.hit")
    np.testing.assert_array_equal(x1, x2)


# --------------------------------------------------- bank: seed/replay
_CHILD = textwrap.dedent("""
    import json, os, sys
    import numpy as np
    from pylops_mpi_tpu import DistributedArray, MPIBlockDiag, aot, cg
    from pylops_mpi_tpu.ops.local import MatrixMult
    tag, outdir = sys.argv[1], sys.argv[2]
    rng = np.random.default_rng(7)
    mats = []
    for _ in range(4):
        a = rng.standard_normal((6, 6)).astype(np.float32)
        mats.append((a @ a.T / 6
                     + 2.0 * np.eye(6, dtype=np.float32))
                    .astype(np.float32))
    Op = MPIBlockDiag([MatrixMult(m, dtype=np.float32) for m in mats])
    rng = np.random.default_rng(3)
    y = DistributedArray(global_shape=24, dtype=np.float32)
    y[:] = rng.standard_normal(24).astype(np.float32)
    x = cg(Op, y, niter=6, tol=0.0, fused=True)[0]
    np.save(os.path.join(outdir, "x_%s.npy" % tag),
            np.asarray(x.asarray()))
    print(json.dumps({"compiles": aot.compile_count()}))
""")


def _run_child(tag, outdir, aot_env):
    env = dict(os.environ, PYLOPS_MPI_TPU_PLATFORM="cpu",
               JAX_PLATFORMS="cpu", **aot_env)
    env.pop("PYLOPS_MPI_TPU_COMPILE_CACHE", None)
    r = subprocess.run([sys.executable, "-c", _CHILD, tag, outdir],
                       env=env, cwd=ROOT, capture_output=True,
                       text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_seed_then_replay_zero_compiles(tmp_path):
    """The headline acceptance: phase 1 (fresh process, empty bank)
    compiles and banks; phase 2 (ANOTHER fresh process, same bank)
    replays with ZERO fresh XLA compiles; both match an ``AOT=off``
    oracle process bit for bit."""
    bank = str(tmp_path / "bank")
    on = {"PYLOPS_MPI_TPU_AOT": "on", "PYLOPS_MPI_TPU_AOT_CACHE": bank}
    seed = _run_child("seed", str(tmp_path), on)
    assert seed["compiles"] >= 1
    assert os.path.exists(os.path.join(bank, "index.json"))
    replay = _run_child("replay", str(tmp_path), on)
    assert replay["compiles"] == 0
    off = _run_child("off", str(tmp_path), {"PYLOPS_MPI_TPU_AOT": "off"})
    assert off["compiles"] == 0
    xs = {t: np.load(str(tmp_path / f"x_{t}.npy"))
          for t in ("seed", "replay", "off")}
    np.testing.assert_array_equal(xs["seed"], xs["off"])
    np.testing.assert_array_equal(xs["replay"], xs["off"])


def _seed_bank(tmp_path, monkeypatch, mats=None, tag=3):
    """Arm AOT with an on-disk bank and run one solve to populate it;
    returns (bank dir, the solved x)."""
    bank = tmp_path / "bank"
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT_CACHE", str(bank))
    mats = mats if mats is not None else _mats()
    n = sum(m.shape[1] for m in mats)
    x = _solve(_op(mats), n, seed=tag)
    assert (bank / "index.json").exists()
    return bank, x


def _forget_process_state():
    """Drop every process-local tier so the next solve must go back
    to the DISK bank (what a fresh process would do)."""
    aot.clear_memory()
    pmt.clear_fused_cache()
    trace.clear_events()


# -------------------------------------------------- bank: robustness
def test_corrupt_index_falls_back(tmp_path, monkeypatch):
    bank, x_seed = _seed_bank(tmp_path, monkeypatch)
    (bank / "index.json").write_text("{ this is not json")
    _forget_process_state()
    x = _solve(_op(_mats()), 24)
    np.testing.assert_array_equal(x, x_seed)
    assert aot.compile_count() == 2     # the replay had to recompile
    evs = _events("aot.cache_error")
    assert evs and "unreadable" in evs[0]["args"]["why"]


def test_schema_mismatch_falls_back(tmp_path, monkeypatch):
    bank, x_seed = _seed_bank(tmp_path, monkeypatch)
    doc = json.loads((bank / "index.json").read_text())
    doc["schema"] = astore.SCHEMA_VERSION + 99
    (bank / "index.json").write_text(json.dumps(doc))
    _forget_process_state()
    x = _solve(_op(_mats()), 24)
    np.testing.assert_array_equal(x, x_seed)
    assert aot.compile_count() == 2
    evs = _events("aot.cache_error")
    assert evs and "schema" in evs[0]["args"]["why"]
    # and the recompile HEALED the file: the next cold lookup replays
    _forget_process_state()
    _solve(_op(_mats()), 24)
    assert aot.compile_count() == 2 and _events("aot.hit")


def test_truncated_payload_falls_back(tmp_path, monkeypatch):
    bank, x_seed = _seed_bank(tmp_path, monkeypatch)
    blobs = [f for f in os.listdir(bank) if f.startswith("exe_")]
    assert blobs
    blob = bank / blobs[0]
    blob.write_bytes(blob.read_bytes()[:max(1, blob.stat().st_size // 2)])
    _forget_process_state()
    x = _solve(_op(_mats()), 24)
    np.testing.assert_array_equal(x, x_seed)
    assert aot.compile_count() == 2
    evs = _events("aot.cache_error")
    assert evs and "payload unusable" in evs[0]["args"]["why"]


def test_unroundtrippable_payload_not_banked(tmp_path, monkeypatch):
    """The store-time round-trip fence: a payload that cannot be
    deserialized (an XLA-compile-cache-hit executable on the CPU
    backend serializes into one) is NEVER written to the bank — the
    solve still runs off the fresh executable (via the Compiled
    wrapper's own out_tree) and the skip is a classified
    ``aot.cache_error``, so later processes pay one compile instead of
    a deserialize-fail-then-fallback every cold start."""
    from pylops_mpi_tpu.aot import executable as aexe
    bank = tmp_path / "bank"
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT_CACHE", str(bank))

    def _refuse(payload, out_tree_bytes):
        raise RuntimeError("synthetic round-trip failure")

    orig = aexe.load_serialized
    monkeypatch.setattr(aexe, "load_serialized", _refuse)
    mats = _mats()
    x = _solve(_op(mats), 24)
    assert aot.compile_count() == 1
    assert not (bank / "index.json").exists()
    evs = _events("aot.cache_error")
    assert evs and any("not banked" in e["args"]["why"] for e in evs)
    monkeypatch.setattr(aexe, "load_serialized", orig)
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "off")
    _forget_process_state()
    x_off = _solve(_op(mats), 24)
    np.testing.assert_array_equal(x, x_off)


@pytest.mark.parametrize("field,value,why", [
    ("jax", "0.0.0", "jax"),
    ("device_kind", "TPU v99", "device_kind"),
    ("n_devices", 1024, "n_devices"),
])
def test_foreign_signature_classified_miss(tmp_path, monkeypatch,
                                           field, value, why):
    """A bank written under a different jax version / chip kind / mesh
    size is a CLASSIFIED miss naming the mismatched field — fresh
    compile, never a deserialize attempt of a foreign executable."""
    bank, x_seed = _seed_bank(tmp_path, monkeypatch)
    doc = json.loads((bank / "index.json").read_text())
    (eid, entry), = doc["entries"].items()
    entry["signature"][field] = value
    (bank / "index.json").write_text(json.dumps(doc))
    _forget_process_state()
    x = _solve(_op(_mats()), 24)
    np.testing.assert_array_equal(x, x_seed)
    assert aot.compile_count() == 2
    evs = _events("aot.cache_error")
    assert evs and why in evs[0]["args"]["why"]


def test_stale_avals_classified_miss(tmp_path, monkeypatch):
    bank, x_seed = _seed_bank(tmp_path, monkeypatch)
    doc = json.loads((bank / "index.json").read_text())
    (eid, entry), = doc["entries"].items()
    entry["avals"] = [["999"], "stale"]
    (bank / "index.json").write_text(json.dumps(doc))
    _forget_process_state()
    x = _solve(_op(_mats()), 24)
    np.testing.assert_array_equal(x, x_seed)
    evs = _events("aot.cache_error")
    assert evs and "avals" in evs[0]["args"]["why"]


def test_wrong_executable_call_time_fallback(tmp_path, monkeypatch):
    """Defense in depth: a blob that deserializes fine but holds the
    WRONG program (index row valid — e.g. a hash collision or a
    hand-mangled bank) is rejected by the executable's own aval fence
    at call time, traced, and replaced by a fresh compile — the answer
    is still exact."""
    bank = tmp_path / "bank"
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT_CACHE", str(bank))
    mats_a, mats_b = _mats(nb=6), _mats(nb=8)
    x_a = _solve(_op(mats_a), 24)
    _solve(_op(mats_b), 32)
    blobs = sorted(f for f in os.listdir(bank) if f.startswith("exe_"))
    assert len(blobs) == 2
    b0, b1 = (bank / blobs[0]), (bank / blobs[1])
    d0, d1 = b0.read_bytes(), b1.read_bytes()
    b0.write_bytes(d1)
    b1.write_bytes(d0)
    _forget_process_state()
    x = _solve(_op(mats_a), 24)
    np.testing.assert_array_equal(x, x_a)
    evs = _events("aot.cache_error")
    assert evs and any("rejected at call time" in e["args"]["why"]
                       for e in evs)


def test_two_process_store_stress(tmp_path):
    """Two PROCESSES hammering ``store_entry`` on the same bank
    concurrently (a prewarm pass racing a live solve elsewhere): the
    flock-serialized read-merge-write plus pid-suffixed temp staging
    must keep index.json valid throughout and lose NO entry."""
    bank = tmp_path / "bank"
    n = 12
    code = textwrap.dedent("""
        import os, sys
        os.environ['PYLOPS_MPI_TPU_AOT_CACHE'] = sys.argv[1]
        from pylops_mpi_tpu.aot import store
        tag = sys.argv[2]
        for i in range(%d):
            store.store_entry((tag, i), {"jax": "x"}, ("aval",),
                              b"payload-" + tag.encode(), b"tree",
                              0.001)
    """ % n)
    env = dict(os.environ, PYLOPS_MPI_TPU_PLATFORM="cpu",
               JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(bank), tag],
        env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE) for tag in ("alpha", "beta")]
    for p in procs:
        _, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
    entries = astore.load_index(str(bank))
    assert len(entries) == 2 * n
    for entry in entries.values():
        blob = bank / entry["payload"]
        assert blob.exists()
        assert pickle.loads(blob.read_bytes())["out_tree"] == b"tree"
    leftovers = [f for f in os.listdir(bank) if f.startswith(".aot_")
                 and not f.endswith(".lock")]
    assert leftovers == []


# ------------------------------------------- serving prewarm signature
def test_prewarm_skips_warmed_signature(monkeypatch):
    """Round-18 regression: with AOT armed, a restarted daemon
    registering a FRESH operator instance of an identical family skips
    the per-bucket zero-RHS recompile outright (signature-keyed, not
    id-keyed) — and the skipped pool still serves bit-identical
    solves."""
    from pylops_mpi_tpu.serving import FamilySpec, WarmPool
    monkeypatch.setenv("PYLOPS_MPI_TPU_AOT", "on")
    mats = _mats()
    rng = np.random.default_rng(11)
    Y = rng.standard_normal((24, 2)).astype(np.float32)

    def _pool():
        pool = WarmPool(buckets=(2,))
        pool.register(FamilySpec(name="fam", operator=_op(mats),
                                 solver="cgls", niter=6, tol=0.0))
        return pool
    p1 = _pool()
    assert p1.prewarm(widths=[2]) == {"fam": [2]}
    c_seed = aot.compile_count()
    assert c_seed >= 1
    x1 = p1.solve("fam", Y).x
    trace.clear_events()
    p2 = _pool()                      # fresh instance, same signature
    assert p2.prewarm(widths=[2]) == {"fam": [2]}
    assert aot.compile_count() == c_seed     # no recompile
    assert _events("serve.prewarm_skip")
    assert ("fam", 2) in p2.warmed
    np.testing.assert_array_equal(p2.solve("fam", Y).x, x1)


def test_prewarm_without_aot_still_compiles(monkeypatch):
    """The conditional's other half: WITHOUT the AOT tier the
    executables live only in the id-keyed fused cache, so a fresh
    instance genuinely needs its zero-RHS compile — prewarm must NOT
    skip it."""
    from pylops_mpi_tpu.serving import FamilySpec, WarmPool
    mats = _mats()

    def _pool():
        pool = WarmPool(buckets=(2,))
        pool.register(FamilySpec(name="fam", operator=_op(mats),
                                 solver="cgls", niter=6, tol=0.0))
        return pool
    p1 = _pool()
    p1.prewarm(widths=[2])
    trace.clear_events()
    p2 = _pool()
    p2.prewarm(widths=[2])
    assert _events("serve.prewarm_skip") == []


# ------------------------------------------------ compilation cache
# The one placement rule (aot/compile_cache.py): JAX_COMPILATION_CACHE_DIR
# stands untouched, else PYLOPS_MPI_TPU_COMPILE_CACHE, else the entry
# script's <checkout>/.jax_cache default, else nothing.
@pytest.fixture
def cache_config(monkeypatch):
    """Clean cache env + jax config restored afterwards, so the rest
    of the suite keeps whatever cache the run was started with."""
    import jax
    from pylops_mpi_tpu.aot import compile_cache as cc
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes,
             cc._enabled_dir,
             jax.config.jax_compilation_cache_include_metadata_in_key)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_COMPILE_CACHE", raising=False)
    cc._enabled_dir = None
    yield cc
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      saved[2])
    cc._enabled_dir = saved[3]
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      saved[4])


def test_compile_cache_unset_is_noop(cache_config):
    import jax
    before = jax.config.jax_compilation_cache_dir
    assert cache_config.compile_cache_dir() is None
    assert cache_config.maybe_enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_entry_default(cache_config, tmp_path):
    """Neither variable set: the entry scripts' default is used, and
    the thresholds are lowered so fast compiles are banked too."""
    import jax
    d = str(tmp_path / ".jax_cache")
    assert cache_config.compile_cache_dir(d) == d
    assert cache_config.maybe_enable_compile_cache(d) == d
    assert jax.config.jax_compilation_cache_dir == d
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    # the names a profile is read by are part of the key
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert cache_config.maybe_enable_compile_cache(d) == d  # idempotent


def test_compile_cache_package_knob_beats_default(cache_config, tmp_path,
                                                  monkeypatch):
    import jax
    knob, d = str(tmp_path / "knob"), str(tmp_path / ".jax_cache")
    monkeypatch.setenv("PYLOPS_MPI_TPU_COMPILE_CACHE", knob)
    assert cache_config.maybe_enable_compile_cache(d) == knob
    assert jax.config.jax_compilation_cache_dir == knob


def test_compile_cache_jax_variable_stands(cache_config, tmp_path,
                                           monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: it is reported as the directory
    in use and nothing here overrides jax_compilation_cache_dir — not
    the package knob, not an entry script's default."""
    import jax
    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    monkeypatch.setenv("PYLOPS_MPI_TPU_COMPILE_CACHE",
                       str(tmp_path / "knob"))
    before = jax.config.jax_compilation_cache_dir
    got = cache_config.maybe_enable_compile_cache(
        str(tmp_path / ".jax_cache"))
    assert got == outside
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / ".jax_cache").exists()
    assert not (tmp_path / "knob").exists()


def test_one_cache_dir_setter_in_the_repo():
    """``jax_compilation_cache_dir`` is set in one place only."""
    import glob
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = glob.glob(os.path.join(root, "*.py"))
    for sub in ("pylops_mpi_tpu", "benchmarks", "examples"):
        paths += glob.glob(os.path.join(root, sub, "**", "*.py"),
                           recursive=True)
    hits = []
    for path in paths:
        with open(path) as f:
            if '"jax_compilation_cache_dir"' in f.read():
                hits.append(os.path.relpath(path, root))
    assert hits == [os.path.join("pylops_mpi_tpu", "aot",
                                 "compile_cache.py")]


# ------------------------------------------------ supervisor wiring
def test_supervisor_injects_aot_env(tmp_path):
    """``launch_job(..., aot_cache=dir)`` arms every worker with the
    bank + the compilation-cache fallback (explicit ``env`` still
    wins); the recovery path that lets relaunched attempts prewarm
    from the bank attempt 0 seeded."""
    from pylops_mpi_tpu.resilience.supervisor import launch_job
    probe = tmp_path / "probe.py"
    probe.write_text(textwrap.dedent("""
        import json, os, sys
        out = {k: os.environ.get("PYLOPS_MPI_TPU_" + k)
               for k in ("AOT", "AOT_CACHE", "COMPILE_CACHE")}
        with open(sys.argv[1], "w") as f:
            json.dump(out, f)
    """))
    seen = tmp_path / "seen.json"
    r = launch_job([str(probe), str(seen)], 1, max_relaunches=0,
                   aot_cache=str(tmp_path / "bank"),
                   job_timeout_s=120.0)
    assert r.ok, r
    got = json.loads(seen.read_text())
    assert got["AOT"] == "on"
    assert got["AOT_CACHE"] == str(tmp_path / "bank")
    assert got["COMPILE_CACHE"] == os.path.join(
        str(tmp_path / "bank"), "xla")


@pytest.mark.slow
def test_supervisor_relaunch_replays_bank(tmp_path):
    """End-to-end recovery acceptance: job 1 (attempt 0) compiles and
    seeds the bank through ``launch_job(aot_cache=...)``; job 2 — the
    same worker command, the relaunch scenario — replays from the bank
    with ZERO fresh compiles and a bit-identical answer."""
    from pylops_mpi_tpu.resilience.supervisor import launch_job
    worker = tmp_path / "worker.py"
    worker.write_text(_CHILD + textwrap.dedent("""
        with open(os.path.join(outdir, "compiles_%s.json" % tag),
                  "w") as f:
            json.dump({"compiles": aot.compile_count()}, f)
    """))
    bank = str(tmp_path / "bank")
    for tag in ("seed", "replay"):
        r = launch_job([str(worker), tag, str(tmp_path)], 1,
                       max_relaunches=0, aot_cache=bank,
                       job_timeout_s=240.0,
                       env={"PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"})
        assert r.ok, r
    seed = json.loads((tmp_path / "compiles_seed.json").read_text())
    replay = json.loads((tmp_path / "compiles_replay.json").read_text())
    assert seed["compiles"] >= 1
    assert replay["compiles"] == 0
    np.testing.assert_array_equal(np.load(str(tmp_path / "x_seed.npy")),
                                  np.load(str(tmp_path / "x_replay.npy")))
