"""The decode kernel's share of its HBM-bandwidth roofline, in percent
(see `chipbench.layers.decode_roofline`)."""
from chipbench.layers import decode_roofline as read  # noqa: F401
