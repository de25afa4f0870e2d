"""Least-squares migration — analog of the reference's
``tutorials/lsm.py``: Kirchhoff demigration blocks (one per shard's
batch of sources) stacked with MPIVStack — model BROADCAST, data
SCATTER, adjoint allreduce — inverted with CGLS. The operator is
PyLops' static Kirchhoff (``models/lsm.py``): constant-velocity
straight rays, two interpolation taps a pixel (``floor`` of the travel
time in samples and its fraction), no amplitude term; the per-pair
tables are made and kept on the device and both applies are the Pallas
kernels ``pmt_kirchhoff`` / ``pmt_kirchhoff_adj`` (interpreted off a
TPU). Not there: eikonal travel times, ``dynamic=True`` weights. Give
``MPILSM`` at least a source a device."""
import _setup  # noqa: F401
import numpy as np
import pylops_mpi_tpu as pmt
from pylops_mpi_tpu.models import lsm, MPILSM, ricker

# velocity model & reflectivity with two interfaces (ref tutorials/lsm.py)
nx, nz = 81, 60
dx, dz = 4, 4
x, z = np.arange(nx) * dx, np.arange(nz) * dz
v0 = 1000.0
refl = np.zeros((nz, nx))
refl[30] = -1.0
refl[50] = 0.5

# receivers & sources (sources get split over the 8 shards)
nr, ns = 11, 16
recs = np.vstack((np.linspace(10 * dx, (nx - 10) * dx, nr),
                  20 * np.ones(nr)))
srcs = np.vstack((np.linspace(10 * dx, (nx - 10) * dx, ns),
                  10 * np.ones(ns)))

nt, dt = 400, 0.002
t = np.arange(nt) * dt
wav, wt = ricker(t[:21], f0=20)
wavc = len(wav) // 2

Op = MPILSM(z, x, t, srcs, recs, v0, wav, wavc)
print("LSM operator:", Op.shape, "(pairs x nt =", ns * nr, "x", nt, ")")

minv, d, cost = lsm(z, x, t, srcs, recs, v0, wav, wavc, refl, niter=100)
print("data norm:", float(np.linalg.norm(d)))
print("cost:", cost[0], "->", cost[-1])
# the two interfaces should be local maxima of the recovered image
energy = np.abs(minv).sum(axis=1)
peaks = [i for i in range(1, nz - 1)
         if energy[i] > energy[i - 1] and energy[i] > energy[i + 1]
         and energy[i] > 0.3 * energy.max()]
print("recovered interfaces (rows):", peaks, "(true: [30, 50])")
