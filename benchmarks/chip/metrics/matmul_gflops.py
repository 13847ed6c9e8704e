"""2·rows·inner·cols per answered request over the window's seconds."""
from chipbench.layers import matmul_gflops as read  # noqa: F401
