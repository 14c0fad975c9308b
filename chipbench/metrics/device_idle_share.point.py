"""Percent of the traced window in which the device ran nothing (see
`chipbench.layers.device_idle_share`)."""
from chipbench.layers import device_idle_share as read  # noqa: F401
