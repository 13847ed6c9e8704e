"""100 × (1 − union of device-op intervals / the window), from the trace."""
from chipbench.layers import device_idle_share as read  # noqa: F401
