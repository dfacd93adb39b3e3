"""Milliseconds a pass in which the engine's loop thread was neither
waiting for the device nor running: ``(wall_seconds - cpu_seconds) /
passes`` of ``engine.stats()["loop"]`` (``wall_seconds`` has the waits
for the device taken out) — the interpreter's lock, the queue's lock,
the scheduler: what a bound step cannot remove. The chip's host moves a
thread's clock in 10-ms ticks, so a run's reading carries +-0.1-0.2 ms;
and ``cpu_seconds`` holds what the thread burns INSIDE its waits (the
fetched array's conversion, the runtime's own calls: 0.2 ms a wait on
that host, whose wall left with the wait), so this reads LOW by that
and a thread that always ran comes out UNDER 0, by about -0.3 on
``serve-chat``. Over the
engine's whole life (ramp, window and drain: the driver keeps one
``stats()``, taken after ``stop``), the same traffic throughout; a pass
that only waited for work is counted nowhere. None without a device
plane (a rehearsal), on a run that is not a serve run and on a program
without the ``loop`` entry."""


def read(run):
    from chipbench.engine_pass import loop_account
    loop = loop_account(run)
    if loop is None:
        return None
    return (loop["wall_seconds"] - loop["cpu_seconds"]) \
        / loop["passes"] * 1e3
