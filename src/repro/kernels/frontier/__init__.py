from .ops import frontier_expand
from .xla import NBR_INF, PullView, frontier_expand_xla, pull_view

__all__ = ["frontier_expand", "frontier_expand_xla", "pull_view", "PullView", "NBR_INF"]
