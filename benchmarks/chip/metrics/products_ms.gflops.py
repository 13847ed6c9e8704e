"""compute_products after the encode: cast, copy in, kernel, copy out; per request."""
from chipbench.layers import products_ms as read  # noqa: F401
