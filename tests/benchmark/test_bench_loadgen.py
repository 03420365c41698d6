"""The serving load generator against a stub server: the schedule is a
function of the seed over one fixed multiset, latency runs from the due
time, and a failed request stays in the tail."""

import http.client
import http.server
import os
import statistics
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import loadgen  # noqa: E402

MIX = {"rate_per_s": 200.0,
       "length": {"median": 160, "sigma": 0.6, "min": 24, "max": 512}}


def test_schedule_is_a_function_of_the_seed():
    a, b = loadgen.schedule(2**31 + 5, MIX, 5.0), loadgen.schedule(
        2**31 + 5, MIX, 5.0)
    assert a == b and len(a) == 1000
    assert all(x[0] < y[0] for x, y in zip(a, a[1:])) and a[-1][0] < 5.0


def test_every_seed_offers_the_same_work_in_another_order():
    a, b = loadgen.schedule(1, MIX, 5.0), loadgen.schedule(2, MIX, 5.0)
    assert a != b
    assert sorted(x[1] for x in a) == sorted(x[1] for x in b)
    gaps = lambda p: sorted(round(y[0] - x[0], 9)            # noqa: E731
                            for x, y in zip([(0.0, 0)] + p, p))
    assert gaps(a) == pytest.approx(gaps(b))


def test_arrivals_are_poisson_and_lengths_lognormal():
    plan = loadgen.schedule(3, MIX, 20.0)
    gaps = [y[0] - x[0] for x, y in zip(plan, plan[1:])]
    mean = statistics.mean(gaps)
    assert mean == pytest.approx(1 / 200.0, rel=0.05)
    # exponential gaps: the deviation is as large as the mean (evenly
    # spaced arrivals would have none)
    assert statistics.pstdev(gaps) == pytest.approx(mean, rel=0.15)
    lengths = [x[1] for x in plan]
    assert statistics.median(lengths) == pytest.approx(160, rel=0.08)
    assert min(lengths) >= 24 and max(lengths) == 512


@pytest.mark.parametrize("q,want", [(50, 3), (95, 5), (100, 5), (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert loadgen.percentile([5, 1, 4, 2, 3], q) == want


class _Stub(http.server.BaseHTTPRequestHandler):
    delay = 0.0
    fail_over = 10**9

    def do_POST(self):
        length = int(self.rfile.read(int(self.headers["Content-Length"])))
        time.sleep(self.delay)
        code = 500 if length > self.fail_over else 200
        self.send_response(code)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def log_message(self, *args):
        pass


@pytest.fixture
def stub():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    host, port = server.server_address

    def send(length):
        conn = http.client.HTTPConnection(host, port, timeout=5)
        try:
            conn.request("POST", "/v1/squad", body=str(length))
            if conn.getresponse().status != 200:
                raise RuntimeError("refused")
        finally:
            conn.close()
        return length

    yield send
    _Stub.delay, _Stub.fail_over = 0.0, 10**9
    server.shutdown()
    server.server_close()


def test_latency_runs_from_the_due_time_not_from_the_send(stub):
    """One worker, 20 ms a request, 100 arrivals a second: the server falls
    behind, every request waits for the ones before it, and latency from
    the due time grows to many service times although each send-to-answer
    time stays 20 ms. The generator's own lateness shows the same queue."""
    _Stub.delay = 0.02
    plan = loadgen.schedule(7, dict(MIX, rate_per_s=100.0), 0.6)
    records = loadgen.run_load(stub, plan, workers=1, timeout_s=5.0)
    out = loadgen.summarize(records, 0.6)
    assert out["attempted"] == len(plan) == 60 and out["failed"] == 0
    service = [r["done"] - r["sent"] for r in records]
    assert statistics.median(service) < 0.04
    assert out["p95_ms"] > 300 and out["lateness_p95_ms"] > 250
    # tokens of requests answered inside the window only
    inside = sum(r["tokens"] for r in records if r["done"] <= 0.6)
    assert out["tokens_per_s"] == pytest.approx(inside / 0.6)
    assert inside < sum(r["tokens"] for r in records)


def test_a_failed_request_counts_as_the_longest(stub):
    _Stub.fail_over = 300       # the long requests are refused
    plan = loadgen.schedule(8, MIX, 0.5)
    records = loadgen.run_load(stub, plan, workers=8, timeout_s=5.0)
    out = loadgen.summarize(records, 0.5)
    refused = sum(1 for _, n in plan if n > 300)
    assert refused >= 5 and out["failed"] == refused
    assert out["attempted"] == len(plan)
    longest = max(r["done"] - r["due"] for r in records)
    assert out["p95_ms"] == pytest.approx(1e3 * longest)   # > 5 % failed
    assert out["lateness_p95_ms"] < 50


def test_an_unanswered_request_is_a_failure(stub):
    _Stub.delay = 1.0
    plan = loadgen.schedule(9, dict(MIX, rate_per_s=20.0), 0.2)
    records = loadgen.run_load(stub, plan, workers=1, timeout_s=0.3)
    out = loadgen.summarize(records, 0.2)
    assert out["attempted"] == 4 and out["failed"] == 4
    assert out["tokens_per_s"] == 0.0
