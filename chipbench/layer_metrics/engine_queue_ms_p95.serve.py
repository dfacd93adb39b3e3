"""95th percentile, over the window's requests, of
``future.admitted_at - future.enqueued_at``: the wait for a slot as the
ENGINE timed it, from its accepting the request to its taking a slot
for the prefill (``queue_wait_ms_p95.serve`` times the same wait from
outside, from the due time to the prefill span). None without a device
plane (a rehearsal) and on a program whose futures carry no
timestamps."""


def read(run):
    from chipbench.program_spans import on_chip, p95_ms
    if not on_chip(run):
        return None
    waits = []
    for r in run.get("requests", []):
        admitted = getattr(r.future, "admitted_at", None)
        enqueued = getattr(r.future, "enqueued_at", None)
        if admitted is not None and enqueued is not None:
            waits.append(admitted - enqueued)
    return p95_ms(waits)
