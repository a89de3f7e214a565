# Compute hot-spot kernels (flash attention, SSD scan, fused Adam), each
# shipped as <name>.py (Pallas TPU) + ops.py (jit wrapper) + ref.py (jnp
# oracle).  ``repro.kernels.dispatch`` is the backend-dispatched registry
# the production call sites go through: TPU -> Pallas (blocks from the
# shape or autotuned), CPU/GPU -> the chunked-jnp reference, overridable
# via REPRO_KERNELS or dispatch.force().
from repro.kernels import dispatch  # noqa: F401
