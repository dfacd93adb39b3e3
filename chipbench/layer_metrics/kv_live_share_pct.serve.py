"""Share of the reserved KV cache that held a live token, averaged over
the window: each request occupies its prompt's positions from its first
token on and one more with every token, until it completes; the pool is
slots x max_seq_len positions (``kv_reserved_bytes`` in the notes is
the same pool in bytes). Dense per-slot caches reserve a whole context
for every slot, so under chat-length traffic most of the pool is never
written: this is the share a paged cache would win back."""


def read(run):
    if run.get("kind") != "serve" or not run.get("kv_reserved_positions"):
        return None
    t0, t1 = run["window"]
    position_seconds = 0.0
    for r in run["all_requests"]:
        if r.first_token is None or r.completed is None:
            continue
        a, b = max(r.first_token, t0), min(r.completed, t1)
        if b <= a:
            continue
        span = max(r.completed - r.first_token, 1e-9)

        def resident(t, r=r, span=span):
            return len(r.prompt) + 1 + (r.answer_len - 1) * \
                (t - r.first_token) / span

        position_seconds += (resident(a) + resident(b)) / 2.0 * (b - a)
    mean_live = position_seconds / (t1 - t0)
    return mean_live / run["kv_reserved_positions"] * 100.0
