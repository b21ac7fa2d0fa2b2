"""85th percentile of time to first token over every request due in the
window, timed from when it was due.  A request with no first token by
the window's end counts at (end - due).  At the cell's 1.6 requests/s
a 51-second window holds about 80 requests, so p85 has a dozen beyond
it."""

from chipbench.record import percentile


def read(run):
    end = run.window_s
    waits = [(r.times[0] if r.times and r.times[0] <= end else end) - r.due
             for r in run.requests if r.due < run.seconds]
    return percentile(waits, 85)
