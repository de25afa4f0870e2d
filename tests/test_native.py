"""Native C++ host-runtime tests: pack/unpack parity with the NumPy
fallback, threaded IO, and the DistributedArray wiring
(ref pad-to-max idiom: pylops_mpi/utils/_nccl.py:363-403; to_dist /
asarray: pylops_mpi/DistributedArray.py:408-461, 371-406)."""

import jax
import numpy as np
import pytest

from pylops_mpi_tpu import DistributedArray, Partition, native


def _numpy_pack(x, axis, sizes, s_phys):
    P = len(sizes)
    shp = list(x.shape)
    shp[axis] = P * s_phys
    out = np.zeros(shp, dtype=x.dtype)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    for p in range(P):
        src = [slice(None)] * x.ndim
        dst = [slice(None)] * x.ndim
        src[axis] = slice(int(offs[p]), int(offs[p + 1]))
        dst[axis] = slice(p * s_phys, p * s_phys + int(sizes[p]))
        out[tuple(dst)] = x[tuple(src)]
    return out


def test_native_available():
    # g++ is part of the baked toolchain; the build must succeed here.
    assert native.available()


def test_local_split_matches_reference_semantics():
    # first n % P shards get the extra element (ref DistributedArray.py:62-71)
    s = native.local_split_native(10, 4)
    assert s.tolist() == [3, 3, 2, 2]
    assert native.local_split_native(8, 4).tolist() == [2, 2, 2, 2]


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.complex64,
                                   np.int32])
@pytest.mark.parametrize("axis", [0, 1, 2])
def test_pack_unpack_roundtrip(rng, dtype, axis):
    shape = [5, 7, 6]
    x = rng.standard_normal(shape).astype(dtype)
    n = shape[axis]
    sizes = native.local_split_native(n, 4)
    s_phys = int(sizes.max())
    packed = native.pack_padded(x, axis, sizes, s_phys)
    assert packed.shape[axis] == 4 * s_phys
    np.testing.assert_array_equal(packed, _numpy_pack(x, axis, sizes, s_phys))
    back = native.unpack_padded(packed, axis, sizes, s_phys)
    np.testing.assert_array_equal(back, x)


def test_pack_large_threaded(rng):
    x = rng.standard_normal((3, 1001, 17)).astype(np.float32)
    sizes = native.local_split_native(1001, 8)
    s_phys = int(sizes.max())
    packed = native.pack_padded(x, 1, sizes, s_phys, nthreads=8)
    back = native.unpack_padded(packed, 1, sizes, s_phys, nthreads=8)
    np.testing.assert_array_equal(back, x)


def test_read_write_binary(tmp_path, rng):
    x = rng.standard_normal((257, 33)).astype(np.float32)
    p = str(tmp_path / "vol.bin")
    native.write_binary(p, x)
    y = native.read_binary(p, np.float32, x.shape)
    np.testing.assert_array_equal(x, y)


def test_read_binary_offset(tmp_path, rng):
    x = rng.standard_normal(100).astype(np.float64)
    p = str(tmp_path / "off.bin")
    native.write_binary(p, x)
    y = native.read_binary(p, np.float64, (90,), offset=10 * 8)
    np.testing.assert_array_equal(x[10:], y)


def test_to_dist_uneven_uses_native_and_matches(rng):
    # 10 rows over 8 shards -> uneven: exercises the native pack path
    P = len(jax.devices())
    # P+1 rows over P shards: uneven at EVERY device count
    x = rng.standard_normal((P + 1, 6)).astype(np.float32)
    d = DistributedArray.to_dist(x, partition=Partition.SCATTER, axis=0)
    np.testing.assert_allclose(d.asarray(), x, rtol=1e-6)
    locs = d.local_arrays()
    assert [la.shape[0] for la in locs] == [2] + [1] * (P - 1)
    np.testing.assert_allclose(np.concatenate(locs, axis=0), x, rtol=1e-6)


def test_negative_axis(rng):
    x = rng.standard_normal((4, 11, 3)).astype(np.float32)
    sizes = native.local_split_native(3, 2)
    s_phys = int(sizes.max())
    packed = native.pack_padded(x, -1, sizes, s_phys)
    np.testing.assert_array_equal(packed,
                                  native.pack_padded(x, 2, sizes, s_phys))
    np.testing.assert_array_equal(
        native.unpack_padded(packed, -1, sizes, s_phys), x)


def test_dot_mismatched_local_shapes(rng):
    # dot between two splits of the same global vector (e.g. a balanced
    # to_dist vector vs a single-block MPIBlockDiag output whose layout
    # is (700,0,...)) must rebalance, not broadcast-fail
    P = len(jax.devices())
    x = rng.standard_normal(P + 2)
    a = DistributedArray.to_dist(x, axis=0)  # balanced 2,2,1,... shards
    b = DistributedArray.to_dist(x, axis=0,
                                 local_shapes=[(P + 2,)] + [(0,)] * (P - 1))
    np.testing.assert_allclose(np.asarray(a.dot(b)), x @ x, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(b.dot(a)), x @ x, rtol=1e-12)


def test_dot_mismatched_axis(rng):
    x = rng.standard_normal((10, 10))
    a = DistributedArray.to_dist(x, axis=0)
    b = DistributedArray.to_dist(x, axis=1)
    np.testing.assert_allclose(np.asarray(a.dot(b)), (x * x).sum(),
                               rtol=1e-12)


def test_checkpoint_blob_sidecar(tmp_path, rng):
    # >=1 MiB arrays go through the native threaded writer sidecar
    from pylops_mpi_tpu.utils import checkpoint
    big = rng.standard_normal((600, 600))  # 2.88 MB
    small = np.arange(5.0)
    p = str(tmp_path / "ck.pkl")
    checkpoint.save_pytree(p, {"big": big, "small": small, "s": 3})
    sidecars = list(tmp_path.glob("ck.pkl.blobs.*"))
    assert len(sidecars) == 1
    back = checkpoint.load_pytree(p)
    np.testing.assert_array_equal(back["big"], big)
    np.testing.assert_array_equal(back["small"], small)
    assert back["s"] == 3
    # re-save replaces the sidecar and removes the orphan
    checkpoint.save_pytree(p, {"big": big + 1})
    sidecars2 = list(tmp_path.glob("ck.pkl.blobs.*"))
    assert len(sidecars2) == 1 and sidecars2[0] != sidecars[0]
    np.testing.assert_array_equal(checkpoint.load_pytree(p)["big"], big + 1)
    # a missing sidecar must raise loudly, not hand back placeholders
    sidecars2[0].unlink()
    with pytest.raises(FileNotFoundError, match="sidecar"):
        checkpoint.load_pytree(p)


def test_fallback_matches_native(rng, monkeypatch):
    x = rng.standard_normal((4, 11, 3)).astype(np.complex64)
    sizes = native.local_split_native(11, 3)
    s_phys = int(sizes.max())
    ref_packed = native.pack_padded(x, 1, sizes, s_phys)
    monkeypatch.setenv("PYLOPS_MPI_TPU_NATIVE", "0")
    fb_packed = native.pack_padded(x, 1, sizes, s_phys)
    np.testing.assert_array_equal(ref_packed, fb_packed)
    fb_back = native.unpack_padded(fb_packed, 1, sizes, s_phys)
    np.testing.assert_array_equal(fb_back, x)


def test_pack_padded_rejects_bad_sizes(rng):
    """Mismatched sizes must raise a Python error, never reach the C++
    memcpy loops (advisor round-1 finding)."""
    x = rng.standard_normal((4, 10))
    good = native.local_split_native(10, 3)
    s_phys = int(good.max())
    with pytest.raises(ValueError, match="sum"):
        native.pack_padded(x, 1, [4, 4, 4], s_phys)  # sum=12 != 10
    with pytest.raises(ValueError, match="s_phys"):
        native.pack_padded(x, 1, [2, 3, 5], 4)  # a size exceeds s_phys
    with pytest.raises(ValueError, match="non-negative"):
        native.pack_padded(x, 1, [4, 4, 4, -2], s_phys)


def test_unpack_padded_rejects_bad_shape(rng):
    x = rng.standard_normal((4, 12))
    with pytest.raises(ValueError, match="len\\(sizes\\)\\*s_phys"):
        native.unpack_padded(x, 1, [4, 3, 3], 5)  # 3*5 != 12
    with pytest.raises(ValueError, match="s_phys"):
        native.unpack_padded(x, 1, [4, 5, 3], 4)  # size 5 > s_phys 4


def test_write_binary_at_streaming(tmp_path, rng):
    """Streaming several arrays into one file at offsets reassembles
    exactly (checkpoint-writer primitive)."""
    a = rng.standard_normal(64).astype(np.float32)
    b = rng.standard_normal(32).astype(np.float32)
    path = str(tmp_path / "stream.bin")
    native.write_binary_at(path, 0, a)
    native.write_binary_at(path, a.nbytes, b)
    back_a = native.read_binary(path, np.float32, (64,))
    back_b = native.read_binary(path, np.float32, (32,), offset=a.nbytes)
    np.testing.assert_array_equal(back_a, a)
    np.testing.assert_array_equal(back_b, b)


def test_read_binary_short_read_raises(tmp_path):
    path = str(tmp_path / "short.bin")
    np.zeros(4, dtype=np.float64).tofile(path)
    with pytest.raises(IOError):
        native.read_binary(path, np.float64, (100,))


def test_pack_unpack_3d_axis_middle(rng):
    """Padded pack/unpack round-trip on a middle axis with a ragged
    split (the layout DistributedArray uses for axis != 0)."""
    x = rng.standard_normal((3, 13, 5))
    sizes = native.local_split_native(13, 8)
    s_phys = int(sizes.max())
    packed = native.pack_padded(x, 1, sizes, s_phys)
    assert packed.shape == (3, 8 * s_phys, 5)
    back = native.unpack_padded(packed, 1, sizes, s_phys)
    np.testing.assert_array_equal(back, x)
    # padding regions are zero-filled
    offs = np.concatenate([[0], np.cumsum(sizes)])
    for p in range(8):
        pad = packed[:, p * s_phys + int(sizes[p]):(p + 1) * s_phys]
        np.testing.assert_array_equal(pad, 0)


def test_local_split_native_matches_python():
    from pylops_mpi_tpu.parallel.partition import Partition, local_split
    for n, p in ((17, 8), (64, 8), (3, 8), (100, 7)):
        nat = native.local_split_native(n, p)
        ref = [s[0] for s in local_split((n,), p, Partition.SCATTER, 0)]
        np.testing.assert_array_equal(nat, ref)
