"""How late the load generator sent requests: the largest (sent - due)
over the requests due in the window that it sent.  Requests are sent
between engine steps, so a step's length bounds it."""


def read(run):
    late = [r.submit - r.due for r in run.requests
            if r.submit is not None and r.due < run.seconds]
    return max(late) if late else None
