"""pylops_mpi_tpu — TPU-native distributed linear operators and solvers.

A ground-up rebuild of PyLops-MPI (reference ``pylops_mpi/__init__.py``)
for TPU: one controller drives a :class:`jax.sharding.Mesh`; MPI/NCCL
collectives become XLA ``psum``/``all_gather``/``all_to_all``/``ppermute``
over ICI/DCN; solver loops run on device as ``lax.while_loop``s.
"""

from .utils.deps import apply_environment as _apply_environment

# Honour the env seams (platform override, x64, matmul precision — the
# last pins true-f32 GEMMs on TPU, see utils/deps.py) before anything
# touches a jax backend.
_apply_environment()

from .parallel.partition import Partition, local_split
from .parallel.mesh import (
    make_mesh, make_mesh_2d, make_mesh_hybrid, initialize_multihost,
    default_mesh, set_default_mesh, best_grid_2d,
)
from .distributedarray import DistributedArray
from .stacked import StackedDistributedArray
from .stackedlinearoperator import MPIStackedLinearOperator
from .linearoperator import (
    MPILinearOperator, LinearOperator, aslinearoperator, asmpilinearoperator,
)
from .ops.blockdiag import MPIBlockDiag, MPIStackedBlockDiag
from .ops.stack import MPIVStack, MPIStackedVStack, MPIHStack
from .ops.derivatives import (MPIFirstDerivative, MPISecondDerivative,
                              MPILaplacian, MPIGradient)
from .ops.matrixmult import MPIMatrixMult
from .ops.halo import MPIHalo, halo_block_split
from .ops.nonstatconv import MPINonStationaryConvolve1D
from .ops.fft import MPIFFTND, MPIFFT2D
from .ops.fredholm import MPIFredholm1
from .ops.mdc import MPIMDC
from .ops.precond import (JacobiPrecond, BlockJacobiPrecond,
                          VCyclePrecond, make_precond)
from .ops.sparse import MPISparseMatrixMult, auto_sparse_matmult
from .solvers.basic import CG, CGLS, cg, cgls, clear_fused_cache
from .solvers.sparsity import ISTA, FISTA, ista, fista
from .solvers.segmented import cg_segmented, cgls_segmented
from .solvers.block import (block_cg, block_cgls, block_cg_segmented,
                            batched_solve, batched_cache_info)
from .solvers.eigs import power_iteration
from .parallel.reshard import (Layout, ReshardError, plan_reshard,
                               reshard_budget)
from .parallel.spill import HostArray
from .resilience import resilient_solve
from .utils.dottest import dottest
from .plotting.plotting import plot_distributed_array, plot_local_arrays

from . import diagnostics
from . import resilience
from . import ops
from . import solvers
from . import utils
from . import parallel
from . import basicoperators
from . import signalprocessing
from . import waveeqprocessing
from . import optimization
from . import plotting
from . import models
from . import serving
from . import aot

# process-wide fallback compile tier: point JAX's persistent
# compilation cache at PYLOPS_MPI_TPU_COMPILE_CACHE (no-op unset) so
# every entry point — tests, scripts, supervised workers, the serving
# daemon — shares the job's cache without per-call wiring (docs/aot.md)
aot.maybe_enable_compile_cache()

__version__ = "0.1.0"
