from .ops import frontier_expand
from .xla import NBR_INF, frontier_expand_xla

__all__ = ["frontier_expand", "frontier_expand_xla", "NBR_INF"]
