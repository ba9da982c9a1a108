"""Share of the traced window in which no operation ran on the device."""
from metrics._shared import idle_share_percent as read  # noqa: F401
