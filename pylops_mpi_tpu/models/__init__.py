"""Application pipelines (L6) — analogs of the reference's tutorials."""
from .poststack import (PoststackLinearModelling, MPIPoststackLinearModelling,
                        poststack_regularized, poststack_inversion, ricker)
from .mdd import mdd, kernel_to_frequency
from .lsm import TravelTimeSpray, KirchhoffDemigration, MPILSM, lsm
