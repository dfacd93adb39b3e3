"""95th percentile, over the window's admitted requests, of prefill
span start less the time the request was due: how long a request
waited for a slot and for the prefills ahead of it."""


def read(run):
    import numpy as np
    waits = [r.admitted - r.due for r in run.get("requests", [])
             if r.admitted is not None]
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
