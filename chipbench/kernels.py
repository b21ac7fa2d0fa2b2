"""Which device ops are the page crypt + MAC kernels, and how many pages
they crossed in the traced window.

The kernels carry no ``name=`` of their own.  The device trace shows
each Pallas kernel as a ``closed_call`` custom call with the target
``tpu_custom_call``, and on the serving path the only such calls are
the page crossing's AES-CTR keystream and fused crypt + MAC kernels, so
``PATTERN`` matches that target.
"""

from __future__ import annotations

import re

from chipbench import trace

PATTERN = re.compile(r":tpu_custom_call$")


def crypt_mac_seconds(red: dict) -> float:
    """Device seconds of the crypt + MAC kernels in a trace reduction."""
    return trace.op_seconds(red, PATTERN)


def traced_pages(run) -> int:
    """Pages read or written by the steps that ended in the traced
    window (decode reads, dirty-page writes and prompt pages written)."""
    lo, hi = run.traced
    return sum(s["pages_read"] + s["pages_written"] for s in run.steps
               if lo <= s["t"] <= hi)
