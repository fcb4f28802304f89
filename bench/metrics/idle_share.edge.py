"""1 - the union of device operation intervals over the traced window, in %."""
from bench.metrics._common import idle_share as read  # noqa: F401
