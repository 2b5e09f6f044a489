from .ops import masked_compact, probe_place
from .xla import masked_compact_xla, probe_place_rounds, probe_place_xla

__all__ = [
    "masked_compact",
    "probe_place",
    "masked_compact_xla",
    "probe_place_xla",
    "probe_place_rounds",
]
