"""run_open wall time and answer read-out minus compute_products, per request."""
from chipbench.layers import host_loop_ms as read  # noqa: F401
