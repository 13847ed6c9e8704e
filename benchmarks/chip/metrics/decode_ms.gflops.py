"""Summed serve.decode_push_seconds of the traced window, per request."""
from chipbench.layers import decode_ms as read  # noqa: F401
