"""Share of the HBM roofline the page crypt + MAC kernels reach: the
bytes the crossings must move (every page read and every dirty page
written in the traced window, from the shapes) over peak HBM bandwidth,
over the kernels' device time."""

from chipbench import kernels, work


def read(run):
    if run.trace is None:
        return None
    s = kernels.crypt_mac_seconds(run.trace)
    pages = kernels.traced_pages(run)
    if not s or not pages:
        return None
    need = work.crossing_bytes(run.config, run.cell["page_tokens"], pages)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / s
