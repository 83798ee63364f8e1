"""Traffic for the serving cells, generated from a traffic file and a
seed: the request stream and the closed-loop pool of clients.

Every seed gets the SAME lengths in the SAME order: the lengths are the
`grid` quantiles of the log-uniform law the traffic file states, walked
in an order drawn from the file's own `order_seed`; `--seed` draws the
token ids (and, in the runner, the weights). With a closed loop of greedy
requests that run to their length, the work of every step is then the
same for every seed, and a difference between two runs is noise of the
system, not of the draw. (Permuting the order by the seed was tried
first: `ttft_ms_p95` then followed the seed, 541 to 626 ms over six seeds
and within 1 % for one seed twice — PERF.md, PR 24.) An open-loop
schedule (arrivals at a rate, latency from the due time) arrives with
the first cell that needs it.
"""
import numpy as np


def log_uniform_grid(lo, hi, n):
    """n lengths at the mid-quantiles of a log-uniform law on [lo, hi]."""
    q = (np.arange(n) + 0.5) / n
    return np.rint(lo * (hi / lo) ** q).astype(int)


def request_stream(traffic, vocab_size, seed):
    """Endless (prompt_ids, max_new_tokens): prompt and output lengths
    each walk a permuted copy of their grid, permuted anew each time
    round; no two prompts share a prefix but by chance."""
    order = np.random.default_rng(traffic['order_seed'])
    content = np.random.default_rng(seed)
    n = traffic['grid']
    prompts = log_uniform_grid(*traffic['prompt_tokens'], n)
    outputs = log_uniform_grid(*traffic['output_tokens'], n)
    while True:
        for p, o in zip(order.permutation(prompts),
                        order.permutation(outputs)):
            yield content.integers(1, vocab_size, int(p)).tolist(), int(o)


class _Client:
    """One request in flight, as its caller sees it."""
    __slots__ = ('handle', 'want', 'seen', 't_submit', 't_last')

    def __init__(self, handle, want, t_submit):
        self.handle, self.want, self.t_submit = handle, want, t_submit
        self.seen, self.t_last = 0, None


class ClosedLoop:
    """`clients` callers, each of which submits, waits for its last
    token, and submits the next at once. One thread: the runner calls
    `fill()` once and `observe(now)` each time a step of the system has
    returned, with the clock read at that return — that is when a client
    sees the tokens the step produced.

    submit(prompt_ids, max_new_tokens) -> handle, or raises to refuse;
    produced(handle) -> tokens delivered so far, or -1 once the system
    has given the request up.
    """

    def __init__(self, clients, stream, submit, produced, clock):
        self.clients, self.stream = clients, stream
        self._submit, self._produced, self._clock = submit, produced, clock
        self.in_flight = []          # _Client records
        self.completed = 0
        self.last_refusal = None
        self.open_window()

    def open_window(self):
        """Count from here: requests sent, refused or wrong, first-token
        times, gaps between tokens, tokens delivered."""
        self.sent = self.failed = self.tokens = 0
        self.ttft_ms, self.gap_ms = [], []
        self.finished = []           # (handle, want) completed since

    def _send(self):
        prompt, want = next(self.stream)
        now = self._clock()
        self.sent += 1
        try:
            handle = self._submit(prompt, want)
        except Exception as e:      # a refusal is a result, not a crash
            self.failed += 1
            self.last_refusal = repr(e)
            return False
        self.in_flight.append(_Client(handle, want, now))
        return True

    def fill(self):
        while len(self.in_flight) < self.clients:
            if not self._send():
                break

    def observe(self, now):
        still = []
        for c in self.in_flight:
            have = self._produced(c.handle)
            if have < 0:
                self.failed += 1
                continue
            if have > c.seen:
                if c.t_last is None:
                    self.ttft_ms.append((now - c.t_submit) * 1e3)
                else:
                    self.gap_ms.append((now - c.t_last) * 1e3)
                # further tokens of one step reach the client together
                self.gap_ms.extend([0.0] * (have - c.seen - 1))
                self.tokens += have - c.seen
                c.seen, c.t_last = have, now
            if have >= c.want:
                self.completed += 1
                self.failed += have != c.want
                self.finished.append((c.handle, c.want))
            else:
                still.append(c)
        self.in_flight = still
        self.fill()
