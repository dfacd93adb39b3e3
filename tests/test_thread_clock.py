"""observability/thread_clock.py: the calling thread's CPU clock and
context switches beside the wall clock (ISSUE 55)."""
import threading
import time

from paddle_tpu.observability import thread_clock


def test_a_reading_is_five_numbers_that_do_not_go_back():
    a = thread_clock.read()
    b = thread_clock.read()
    assert a._fields == ("wall", "cpu", "voluntary", "involuntary",
                         "process_cpu")
    for x, y in zip(a, b):
        assert y >= x
    assert isinstance(a.voluntary, int) and isinstance(a.involuntary, int)


def test_a_sleep_is_wall_and_a_voluntary_switch_not_cpu():
    a = thread_clock.read()
    time.sleep(0.05)
    b = thread_clock.read()
    assert b.wall - a.wall >= 0.05
    assert b.cpu - a.cpu < 0.02
    assert b.voluntary > a.voluntary


def test_a_busy_loop_is_cpu_finer_than_a_scheduler_tick():
    a = thread_clock.read()
    until = time.thread_time() + 0.0015     # under any tick there is
    while time.thread_time() < until:
        pass
    b = thread_clock.read()
    assert 0.0015 <= b.cpu - a.cpu <= b.wall - a.wall + 1e-4
    assert b.process_cpu - a.process_cpu >= 0.0015


def test_the_thread_clock_is_the_calling_threads_own():
    burned = {}

    def burn():
        a = thread_clock.read()
        until = time.thread_time() + 0.03
        while time.thread_time() < until:
            pass
        burned["cpu"] = thread_clock.read().cpu - a.cpu

    a = thread_clock.read()
    t = threading.Thread(target=burn)
    t.start()
    t.join()
    b = thread_clock.read()
    assert burned["cpu"] >= 0.03
    assert b.cpu - a.cpu < 0.02              # the other thread's is not ours
    assert b.process_cpu - a.process_cpu >= 0.03   # but it is the process's


def test_without_rusage_thread_the_switch_counts_are_none(monkeypatch):
    monkeypatch.setattr(thread_clock, "_RUSAGE_THREAD", None)
    r = thread_clock.read()
    assert r.voluntary is None and r.involuntary is None
    assert r.cpu >= 0 and r.process_cpu >= r.cpu * 0 and r.wall > 0


def test_a_reading_costs_microseconds():
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        thread_clock.read()
    assert (time.perf_counter() - t0) / n < 50e-6
