"""Share of the traced window in which no operation ran on the device, in
% (1 - union of op intervals / window), in a cell judged on latency."""
from metrics_common import idle_share as read  # noqa: F401
