"""Host encode (_encode_batch), per request."""
from chipbench.layers import encode_ms as read  # noqa: F401
