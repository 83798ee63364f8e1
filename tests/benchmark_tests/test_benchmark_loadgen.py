"""benchmarks/loadgen.py: the seeded request stream and the closed-loop
pool, against a fake system that emits one token a step."""
import pytest

from benchmarks import common, loadgen

MIX = {'prompt_tokens': [64, 1024], 'output_tokens': [32, 256], 'grid': 256,
       'order_seed': 0}


def take(stream, n):
    return [next(stream) for _ in range(n)]


def test_the_grid_follows_the_log_uniform_law():
    g = loadgen.log_uniform_grid(64, 1024, 256)
    assert g.min() >= 64 and g.max() <= 1024 and len(g) == 256
    assert g.mean() == pytest.approx((1024 - 64) / 2.7726, rel=0.01)
    # as many lengths in [64, 256) as in [256, 1024]
    assert (g < 256).sum() == 128


@pytest.mark.parametrize('seed', [0, 1, 2 ** 31 + 7])
def test_every_seed_offers_the_same_lengths_in_the_same_order(seed):
    base = take(loadgen.request_stream(MIX, 50304, 12345), 600)
    other = take(loadgen.request_stream(MIX, 50304, seed), 600)
    assert [(len(p), o) for p, o in other] == [(len(p), o) for p, o in base]
    assert [p for p, _ in other] != [p for p, _ in base]     # other tokens
    assert all(1 <= t < 50304 for p, _ in other[:8] for t in p)
    # each time round the grid: every length once, in a new order
    first, second = base[:256], base[256:512]
    assert sorted(len(p) for p, _ in first) == sorted(
        len(p) for p, _ in second) == sorted(
        loadgen.log_uniform_grid(64, 1024, 256))
    assert [len(p) for p, _ in first] != [len(p) for p, _ in second]


def test_the_order_comes_from_the_traffic_file():
    other = dict(MIX, order_seed=1)
    a = take(loadgen.request_stream(MIX, 50304, 5), 256)
    b = take(loadgen.request_stream(other, 50304, 5), 256)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert [o for _, o in a] != [o for _, o in b]


def test_the_same_seed_gives_the_same_requests():
    a = take(loadgen.request_stream(MIX, 50304, 2 ** 31 + 7), 300)
    assert a == take(loadgen.request_stream(MIX, 50304, 2 ** 31 + 7), 300)


class FakeSystem:
    """Emits one token per request per step; refuses or drops on demand."""

    def __init__(self):
        self.live, self.refuse, self.abort = [], False, None

    def submit(self, prompt, want):
        if self.refuse:
            raise RuntimeError('pool exhausted')
        handle = {'got': 0}
        self.live.append(handle)
        return handle

    def produced(self, handle):
        return -1 if handle is self.abort else handle['got']

    def step(self):
        for h in self.live:
            h['got'] += 1


def constant_stream(want):
    while True:
        yield [1, 2, 3], want


def test_closed_loop_keeps_its_clients_and_counts_what_a_client_sees():
    sys_, now = FakeSystem(), [0.0]
    pool = loadgen.ClosedLoop(4, constant_stream(3), sys_.submit,
                              sys_.produced, lambda: now[0])
    pool.fill()
    assert pool.sent == 4 and len(pool.in_flight) == 4
    for _ in range(3):                  # 3 steps of 10 ms: all four finish
        sys_.step()
        now[0] += 0.010
        pool.observe(now[0])
    assert pool.completed == 4 and len(pool.finished) == 4
    assert len(pool.in_flight) == 4 and pool.sent == 8      # next at once
    assert pool.tokens == 12 and pool.failed == 0
    assert pool.ttft_ms == pytest.approx([10.0] * 4)
    assert pool.gap_ms == pytest.approx([10.0] * 8)
    # the window's counters restart; requests in flight carry over
    pool.open_window()
    sys_.step()
    now[0] += 0.010
    pool.observe(now[0])
    assert (pool.sent, pool.tokens, len(pool.ttft_ms)) == (0, 4, 4)


def test_a_refusal_and_a_dropped_request_count_as_failed():
    sys_, now = FakeSystem(), [0.0]
    pool = loadgen.ClosedLoop(2, constant_stream(2), sys_.submit,
                              sys_.produced, lambda: now[0])
    pool.fill()
    sys_.abort = pool.in_flight[0].handle
    sys_.refuse = True
    sys_.step()
    pool.observe(now[0])
    # one dropped, and its replacement refused: two failures, one client
    # short until the system takes requests again
    assert pool.failed == 2 and len(pool.in_flight) == 1
    assert 'pool exhausted' in pool.last_refusal
    sys_.refuse = False
    sys_.step()
    pool.observe(now[0])
    assert len(pool.in_flight) == 2


def test_tokens_of_one_step_reach_the_client_together():
    sys_, now = FakeSystem(), [0.0]
    pool = loadgen.ClosedLoop(1, constant_stream(8), sys_.submit,
                              sys_.produced, lambda: now[0])
    pool.fill()
    for _ in range(2):                  # a fused window: 3 tokens a step
        for _ in range(3):
            sys_.step()
        now[0] += 0.030
        pool.observe(now[0])
    assert pool.ttft_ms == pytest.approx([30.0])
    assert pool.gap_ms == pytest.approx([0.0, 0.0, 30.0, 0.0, 0.0])


def test_percentile_is_the_nearest_rank():
    values = list(range(1, 201))
    assert common.percentile(values, 95) == 190      # ten samples beyond
    assert common.percentile(values, 50) == 100
    assert common.percentile([5.0], 95) == 5.0
