from repro.kernels.moe_route.ops import moe_route  # noqa: F401
from repro.kernels.moe_route.ref import moe_route_ref  # noqa: F401
