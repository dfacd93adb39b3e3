"""95th percentile, over the window's requests, of
``future.first_token_at`` less the time the request was due: the first
token OBSERVED on the request itself, beside ``ttft_ms_p95``, which
infers it from the order of the prefill spans. Requests that never got
a first token are left out (``ttft_ms_p95`` counts them as the largest
latency). None without a device plane (a rehearsal) and on a program
whose futures carry no timestamps."""


def read(run):
    from chipbench.program_spans import on_chip, p95_ms
    if not on_chip(run):
        return None
    firsts = []
    for r in run.get("requests", []):
        first = getattr(r.future, "first_token_at", None)
        if first is not None:
            firsts.append(first - r.due)
    return p95_ms(firsts)
