"""The open-loop generator: same work for every seed, latencies from
the due time, FIFO matching of prefill spans to requests."""
import collections

import numpy as np

from chipbench.drivers import serve
from chipbench.spans import Span
from chipbench.traffic import serve as traffic

TRAFFIC = {"rate_per_s": 50.0, "ramp_s": 1.0, "base_seed": 24,
           "prompt_len": {"median": 128, "sigma": 1.0, "min": 16,
                          "max": 512},
           "answer_len": {"median": 64, "sigma": 0.8, "min": 8,
                          "max": 256}}


def _sizes(reqs, in_window):
    return collections.Counter((len(r.prompt), r.answer_len)
                               for r in reqs if r.in_window == in_window)


def test_every_seed_same_arrivals_and_sizes_in_another_order():
    a = traffic.schedule(TRAFFIC, 1, 8.0, 1000)
    b = traffic.schedule(TRAFFIC, 3_000_000_001, 8.0, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert _sizes(a, True) == _sizes(b, True)
    assert _sizes(a, False) == _sizes(b, False)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[0].prompt != b[0].prompt
    again = traffic.schedule(TRAFFIC, 1, 8.0, 1000)
    assert [r.prompt for r in a] == [r.prompt for r in again]
    lens = np.array([len(r.prompt) for r in a])
    assert lens.min() >= 16 and lens.max() <= 512
    assert 300 < len(a) < 600 and all(
        x.due <= y.due for x, y in zip(a, a[1:]))
    assert sum(not r.in_window for r in a) > 20      # the ramp is there


def test_shorter_window_is_a_prefix():
    long = traffic.schedule(TRAFFIC, 7, 8.0, 1000)
    short = traffic.schedule(TRAFFIC, 7, 3.0, 1000)
    assert [r.due for r in short] == [r.due for r in long][:len(short)]


def test_fifo_matching_skips_a_shed_request_and_times_from_due():
    reqs = [traffic.Request(0.0, [1, 2], 3, True),
            traffic.Request(0.1, [1], 2, True),     # shed: no future
            traffic.Request(0.2, [1, 2, 3], 2, True),
            traffic.Request(0.3, [5], 4, True)]     # never admitted
    for i in (0, 2, 3):
        reqs[i].future = object()
    spans = [Span("generation::prefill[2]", 1.00, 0.01, 1.011),
             Span("generation::decode_step[16]", 1.02, 0.01, 1.031),
             Span("generation::prefill[3]", 1.04, 0.01, 1.051),
             Span("pipeline::dispatch", 1.05, 0.001, 1.06),
             Span("generation::decode_step[16]", 1.06, 0.01, 1.071),
             Span("generation::decode_step[16]", 1.08, 0.01, 1.091)]
    assert serve.match_first_tokens(reqs, spans) == 0
    a, shed, b, late = reqs
    # 3 tokens: prefill + the two decode steps that follow it
    assert (a.admitted, a.first_token, a.implied) == (1.00, 1.011, 1.071)
    # 2 tokens: prefill + the first decode step AFTER its prefill
    assert (b.admitted, b.first_token, b.implied) == (1.04, 1.051, 1.071)
    assert shed.first_token is None and shed.implied is None
    assert late.first_token is None
    # only a client sees a completion: the spans fill none in
    assert all(r.completed is None for r in reqs)
    # latency counts from when the request was DUE, not from the submit
    assert abs((a.first_token - a.due) - 1.011) < 1e-12


def test_a_request_whose_steps_never_came_is_incomplete():
    r = traffic.Request(0.0, [1], 5, True)
    r.future = object()
    assert serve.match_first_tokens([r], [
        Span("generation::prefill[1]", 0.0, 0.01, 0.01),
        Span("generation::decode_step[16]", 0.02, 0.01, 0.03)]) == 0
    assert r.first_token == 0.01 and r.implied is None


def test_spans_that_do_not_fit_their_request_are_counted():
    """A program that batches or chunks prefill closes spans that are
    not one a request with its prompt's length: the FIFO reading is
    then wrong, and the run is told so (``correct`` needs 0)."""
    def reqs():
        out = [traffic.Request(0.0, [1, 2], 2, True),
               traffic.Request(0.1, [1, 2, 3], 2, True)]
        for r in out:
            r.future = object()
        return out

    decode = Span("generation::decode_step[16]", 1.06, 0.01, 1.071)
    chunked = [Span("generation::prefill[2]", 1.00, 0.01, 1.011),
               Span("generation::prefill[2]", 1.02, 0.01, 1.031),
               Span("generation::prefill[1]", 1.04, 0.01, 1.051), decode]
    rs = reqs()
    # the second span is not the second request's, the third is nobody's
    assert serve.match_first_tokens(rs, chunked) == 2
    assert rs[0].first_token == 1.011 and rs[1].first_token is None
    batched = [Span("generation::prefill[5]", 1.00, 0.01, 1.011), decode]
    rs = reqs()
    assert serve.match_first_tokens(rs, batched) == 1
    assert rs[0].first_token is None


def test_a_client_thread_observes_the_completion():
    import threading
    import time

    class Future:
        def __init__(self):
            self.ev = threading.Event()

        def result(self, timeout=None):
            if not self.ev.wait(timeout):
                raise TimeoutError
            return "tokens"

    done, late = (traffic.Request(0.0, [1], 2, True) for _ in range(2))
    done.future, late.future = Future(), Future()
    now = time.perf_counter()
    th = threading.Thread(target=serve._await, args=(done, now + 5.0))
    th.start()
    time.sleep(0.05)
    set_at = time.perf_counter()
    done.future.ev.set()
    th.join()
    assert done.result == "tokens" and done.error is None
    assert set_at <= done.completed <= set_at + 1.0
    serve._await(late, now)               # the deadline has passed
    assert late.completed is None and late.error == "TimeoutError"


def test_live_share_of_the_reserved_cache():
    from chipbench.manifest import Manifest
    import chipbench, os
    m = Manifest(os.path.dirname(os.path.dirname(chipbench.__file__)))
    read = m.load_reader("kv_live_share_pct.serve").read
    # one request: 10 prompt tokens, 11 tokens generated over the whole
    # 2-s window: 11 positions at the start, 21 at the end, 16 on average
    r = traffic.Request(0.0, list(range(10)), 11, True)
    r.first_token, r.completed = 1.0, 3.0
    gone = traffic.Request(0.0, [1] * 50, 4, False)     # before the window
    gone.first_token, gone.completed = 0.2, 0.9
    never = traffic.Request(0.0, [1] * 50, 4, True)     # did not complete
    never.first_token = 1.5
    run = {"kind": "serve", "window": (1.0, 3.0),
           "all_requests": [r, gone, never], "kv_reserved_positions": 64}
    assert abs(read(run) - 16.0 / 64 * 100) < 1e-9
    # half the window: positions 11..16, 13.5 on average, for 1 s of 2
    run["window"] = (0.0, 2.0)
    r.first_token, r.completed = 1.0, 3.0
    gone.completed = gone.first_token = None
    assert abs(read(run) - 13.5 / 2 / 64 * 100) < 1e-9
    assert read({"kind": "train"}) is None


def test_serve_chat_lengths_follow_their_sources():
    """The cell's lognormals give the means its file cites (ShareGPT
    prompts 161, Alpaca answers 58; vLLM paper, Fig. 11), and its
    deepest context is the model's."""
    import json, os, chipbench
    root = os.path.dirname(chipbench.__file__)
    cell = json.load(open(os.path.join(
        root, "workloads", "decoder-lm-base.serve-chat.json")))["traffic"]
    cfg = json.load(open(os.path.join(
        root, "configs", "decoder-lm-base.json")))
    reqs = traffic.schedule(dict(cell, rate_per_s=2000.0), 1, 40.0, 1000)
    prompts = np.array([len(r.prompt) for r in reqs])
    answers = np.array([r.answer_len for r in reqs])
    assert abs(prompts.mean() - 161.31) < 5
    assert abs(answers.mean() - 58.45) < 2
    assert cell["prompt_len"]["max"] + cell["answer_len"]["max"] == \
        cfg["max_seq_len"] == cfg["builder"]["args"]["max_seq_len"]
    assert prompts.max() > cfg["builder"]["args"]["prompt_buckets"][-2]
    assert cfg["builder"]["args"]["prompt_buckets"][-1] >= \
        cell["prompt_len"]["max"]
