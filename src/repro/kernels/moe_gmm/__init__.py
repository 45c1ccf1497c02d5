from .ops import moe_gmm
from .ref import moe_gmm_ref

__all__ = ["moe_gmm", "moe_gmm_ref"]
