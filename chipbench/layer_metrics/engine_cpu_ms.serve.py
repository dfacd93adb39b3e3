"""CPU milliseconds of the engine's loop thread a pass: ``cpu_seconds /
passes`` of ``engine.stats()["loop"]`` — Python that RUNS: what a bound
step and a leaner engine half can remove — with what the thread burns
INSIDE its waits for the device (the fetched array's conversion, the
runtime's own calls: 0.2 ms a wait on the chip's host, 0.3 ms a pass on
``serve-chat``), so it reads HIGH by that and ``engine_off_cpu_ms.serve``
low. The chip's host moves a thread's clock in 10-ms ticks: a run's sum
is right to about 2 %. Over the engine's whole life (ramp,
window and drain: the driver keeps one ``stats()``, taken after
``stop``), the same traffic throughout; a pass that only waited for
work is counted nowhere. None without a device plane (a rehearsal), on
a run that is not a serve run and on a program without the ``loop``
entry."""


def read(run):
    from chipbench.engine_pass import loop_account
    loop = loop_account(run)
    if loop is None:
        return None
    return loop["cpu_seconds"] / loop["passes"] * 1e3
