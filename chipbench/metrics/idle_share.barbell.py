"""Device idle share of the traced slice: 1 - (union of the intervals in
which an op ran) / slice length, in percent."""
from chipbench.layers import idle_share as read  # noqa: F401
