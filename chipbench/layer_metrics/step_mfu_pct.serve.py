"""Model FLOPs utilisation of the whole served path: the model FLOPs
(the configuration's ``arith`` module: matrix products, the scans, the
attention over the context, the head) of every prompt prefilled and
every token decoded in the window, over the window's wall time and the
bf16 peak of the chip. A prompt counts where its
``generation::prefill[n]`` span ended in the window; a request's decoded
tokens are spread evenly from its first token to its completion, as
``kv_live_share_pct.serve`` spreads its positions, and count with the
context they had. It is the share of the peak the server as a whole
reaches, idle time included: decode is bound by bytes, so it reads a few
per cent. None off the chip (a rehearsal has no table of peaks), for a
configuration without such an ``arith`` and on a run without requests."""


def read(run):
    import importlib
    from chipbench.program_spans import in_window
    peaks, cfg = run.get("peaks"), run["config"]
    if not peaks or run.get("reduced") is None \
            or run.get("kind") != "serve" or "arith" not in cfg:
        return None
    arith = importlib.import_module(cfg["arith"])
    if not hasattr(arith, "decode_token_flops"):
        return None
    t0, t1 = run["window"]
    flops = 0.0
    for s in in_window(run, ("generation::prefill[",)):
        n = int(s.name[s.name.index("[") + 1:].rstrip("]"))
        flops += arith.prefill_flops(n, **cfg)
    for r in run["all_requests"]:
        if r.first_token is None or r.completed is None \
                or r.answer_len < 2:
            continue
        a, b = max(r.first_token, t0), min(r.completed, t1)
        if b <= a:
            continue
        span = max(r.completed - r.first_token, 1e-9)
        decoded = (r.answer_len - 1) * (b - a) / span
        # the context midway through the part that fell in the window
        context = len(r.prompt) + 1 + (r.answer_len - 1) \
            * ((a + b) / 2 - r.first_token) / span
        flops += decoded * arith.decode_token_flops(context, **cfg)
    if not flops:
        return None
    return flops / (t1 - t0) / peaks["bf16_flops_per_s"] * 100.0
