"""1 - union of the device's operation intervals over the traced window."""
from chipbench.readers import idle_share_percent as read  # noqa: F401
