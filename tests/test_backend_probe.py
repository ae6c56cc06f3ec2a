"""Device-backend probe (ISSUE 14 satellite; xla_compat.py).

A TPU path can die AT SETUP — client construction aborting before any
phase runs. `probe_device_backend` detects that in a throwaway
subprocess and `require_device_backend` turns it into the NAMED
AcceleratorUnavailableError.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from xla_compat import (AcceleratorUnavailableError,  # noqa: E402
                        probe_device_backend, require_device_backend)


def test_probe_cpu_backend_usable():
    verdict, detail = probe_device_backend("cpu", timeout=240.0)
    assert verdict is True, detail
    assert detail.startswith("cpu")


def test_probe_bogus_backend_definitively_unusable():
    verdict, detail = probe_device_backend("nosuchaccelerator",
                                           timeout=240.0)
    assert verdict is False
    assert "died at setup" in detail


def test_require_raises_named_error():
    with pytest.raises(AcceleratorUnavailableError,
                       match="nosuchaccelerator"):
        require_device_backend("nosuchaccelerator", timeout=240.0)
    # and the usable path returns the detail string
    assert require_device_backend("cpu", timeout=240.0).startswith("cpu")
