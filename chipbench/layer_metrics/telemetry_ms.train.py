"""Mean per step of ``trainer::telemetry``: the Trainer's per-step
metrics block (step counters and histograms, the prefetch gauge, the
memory plan's peak, the phase breakdown, the MFU gauge), which runs
after the step's root span while the device waits for the next
dispatch. None without a device plane (a rehearsal) and on a program
without this span."""


def read(run):
    from chipbench.program_spans import ms_per_step
    return ms_per_step(run, ("trainer::telemetry",))
