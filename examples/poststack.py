"""Post-stack seismic inversion — analog of the reference's
``tutorials/poststack.py`` (BASELINE config #4)."""
import _setup  # noqa: F401
import numpy as np
from pylops_mpi_tpu.models import (ricker, MPIPoststackLinearModelling,
                                   poststack_inversion)
from pylops_mpi_tpu import DistributedArray

rng = np.random.default_rng(7)
nx, nt0 = 16, 128
wav, _ = ricker(np.arange(0, 0.02, 0.002), f0=25)

# layered impedance model
m = np.cumsum(rng.standard_normal((nx, nt0)) * 0.03, axis=1) + 2.0

Op = MPIPoststackLinearModelling(wav, nt0, nx)
dm = DistributedArray.to_dist(m.ravel(), local_shapes=Op.local_shapes_m)
d = Op.matvec(dm).asarray().reshape(nx, nt0)
print("modelled data range:", d.min(), d.max())

minv, _ = poststack_inversion(d, wav, niter=100, damp=1e-3)
dre = Op.matvec(DistributedArray.to_dist(
    minv.ravel(), local_shapes=Op.local_shapes_m)).asarray().reshape(nx, nt0)
print("data residual:", np.linalg.norm(dre - d) / np.linalg.norm(d))

minv_reg, _ = poststack_inversion(d, wav, niter=100, epsR=1e-2, damp=1e-3)
print("regularized inversion done; model range:",
      minv_reg.min(), minv_reg.max())

# 3-D, as the reference tutorial: a cube (ny, nx, nt0) sharded on
# inlines, a Laplacian over all three axes, started from a background
ny = 8
m3 = np.cumsum(rng.standard_normal((ny, nx, nt0)) * 0.03, axis=2) + 2.0
Op3 = MPIPoststackLinearModelling(wav, nt0, (ny, nx))
d3 = Op3.matvec(DistributedArray.to_dist(
    m3.ravel(), local_shapes=Op3.local_shapes_m)).asarray().reshape(m3.shape)
kernel = np.hanning(21) / np.hanning(21).sum()
mback = np.apply_along_axis(np.convolve, 2, np.pad(
    m3, ((0, 0), (0, 0), (10, 10)), mode="edge"), kernel, mode="valid")
minv3, _ = poststack_inversion(d3, wav, niter=30, epsR=1e-2, damp=0.0,
                               x0=mback)
dre3 = Op3.matvec(DistributedArray.to_dist(
    minv3.ravel(), local_shapes=Op3.local_shapes_m)).asarray()
print("3-D regularized inversion from a background: data residual",
      np.linalg.norm(dre3 - d3.ravel()) / np.linalg.norm(d3))
