"""What the readers of the token server's PASS share (a pass of the
engine's driver loop: the head that takes the queue's lock and drains
it, then one ``generation::iteration``): the spans that tile the loop
thread's timeline — ``generation::idle_wait``, ``generation::collect``,
``generation::iteration`` — the closed ``generation::stall`` spans, and
the loop thread's own account of its passes, ``engine.stats()["loop"]``
(the ``paddle_tpu_decode_loop_*`` counters).

Every reader returns None without a device plane (``run["reduced"]``
None: a rehearsal), on a run that is not a serve run, and on a program
without the span or the ``loop`` entry (the parent of the PR that added
them).

The two counter readers cover the engine's WHOLE life, not the window:
the drivers keep one ``engine.stats()``, taken after ``stop``, so ramp,
window and drain are in it — the same traffic throughout, and a pass
that only waited for work is counted nowhere."""
from __future__ import annotations

from chipbench.program_spans import in_window, on_chip


def serve_on_chip(run) -> bool:
    return on_chip(run) and run.get("kind") == "serve"


def spans(run, name) -> list:
    """The named spans that ended in the window; [] on a run no reader
    of the pass reads."""
    return in_window(run, (name,)) if serve_on_chip(run) else []


def has_pass_spans(run) -> bool:
    """Does the program tile its loop thread's timeline? Every pass has
    a ``generation::collect``; a program without one has none of the
    pass's spans, and 0 seconds of a span it lacks would be a lie."""
    return bool(spans(run, "generation::collect"))


def loop_account(run):
    """``engine.stats()["loop"]`` of a serve run on the chip that made
    at least one pass, else None."""
    if not serve_on_chip(run):
        return None
    loop = (run.get("engine_stats") or {}).get("loop")
    return loop if loop and loop.get("passes") else None
