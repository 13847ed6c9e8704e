"""Shared test fixtures/paths.

``tools/`` holds standalone scripts (no package), but their logic —
trace validation, the sac_top dashboard/attribution CLI — is under test;
put the directory on the import path so tests import them by module name.
"""
import os
import sys

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)


@pytest.fixture
def encode_buffers(monkeypatch):
    """A fresh pool of the batch encode's host buffers, in place of the
    module's, so no other test's buffers are lent or counted."""
    from repro.serving import backends
    pool = backends._EncodeBuffers()
    monkeypatch.setattr(backends, "_ENCODE_BUFFERS", pool)
    return pool
