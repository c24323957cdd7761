from repro.kernels.xor_gather.ops import gather_decode  # noqa: F401
from repro.kernels.xor_gather.ref import gather_decode_ref  # noqa: F401
