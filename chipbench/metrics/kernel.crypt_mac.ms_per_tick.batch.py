"""Device time of the page crypt + MAC kernels per decode tick."""

from chipbench import kernels
from chipbench.record import traced_decode_steps


def read(run):
    if run.trace is None:
        return None
    s = kernels.crypt_mac_seconds(run.trace)
    ticks = traced_decode_steps(run)
    return 1e3 * s / ticks if s and ticks else None
