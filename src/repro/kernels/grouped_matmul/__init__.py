from .ops import grouped_matmul  # noqa: F401
from .ref import grouped_matmul_ref  # noqa: F401
