"""What one run recorded, and the arithmetic the metric readers share.

The harness fills a :class:`Run`; each metric reader
(``chipbench/metrics/<metric>.py``) is a function ``read(run)`` that
returns one number, or ``None`` where the run holds nothing for it to
read.  Times are seconds on the harness's clock, counted from the
window's opening.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from chipbench import work


@dataclasses.dataclass
class ReqRecord:
    prompt_len: int
    due: float                    # when it was to be sent
    submit: float | None = None   # when it was sent (None: never)
    times: list = dataclasses.field(default_factory=list)  # token times


@dataclasses.dataclass
class Run:
    config: dict
    cell: dict
    peaks: dict
    seconds: float                # the window asked for
    window_s: float = 0.0         # the window as run (to the last step's end)
    setup_s: float = 0.0
    peak_bytes: int = 0
    requests: list = dataclasses.field(default_factory=list)
    # One dict per step of the window: its end time "t", decode
    # dispatches "decodes", and KV pages it read and wrote.
    steps: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)  # window deltas
    spans: dict = dataclasses.field(default_factory=dict)     # name -> s
    trace: dict | None = None     # trace.reduce() of the traced window
    traced: tuple | None = None   # (start, end) of the traced window


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def tokens_in(run: Run, lo: float, hi: float) -> list:
    """(request, token index) of every token emitted in [lo, hi]."""
    return [(r, k) for r in run.requests for k, t in enumerate(r.times)
            if lo <= t <= hi]


def decode_model_flops(run: Run, lo: float, hi: float) -> float:
    """Model FLOPs of the decoded tokens emitted in [lo, hi]: one decode
    step each, at its context (a first token comes from the prefill and
    is left out)."""
    return float(sum(work.decode_flops(run.config, r.prompt_len + k)
                     for r, k in tokens_in(run, lo, hi) if k > 0))


def traced_decode_steps(run: Run) -> int:
    """Decode dispatches whose step ended inside the traced window."""
    lo, hi = run.traced
    return sum(s["decodes"] for s in run.steps if lo <= s["t"] <= hi)
