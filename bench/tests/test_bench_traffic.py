"""The one traffic generator: deterministic from the seed, the same work
for every seed in another order; and the loop's pre-roll, on an engine
stand-in with a clock of its own."""
import collections
import dataclasses

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from loadgen import LoadGen, staggered
from spec import BENCH, read_json
from traffic import make_jobs, quantile_lengths, warm_prompt_lengths

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
SEEDS = [0, 7, 2**31 + 5, 3 * 2**40 + 1, -3]


def _mix(name):
    return read_json(BENCH / "traffic" / f"{name}.json")


def _key(jobs):
    return [(j.prompt.tolist(), j.max_new) for j in jobs]


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_jobs(name, seed):
    tr = _mix(name)
    assert _key(make_jobs(tr, 151936, seed)) == \
        _key(make_jobs(tr, 151936, seed))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    tr = _mix(name)
    a = make_jobs(tr, 151936, 1)
    b = make_jobs(tr, 151936, 2**33 + 9)
    assert _key(a) != _key(b) and len(a) == len(b) == tr["pool"]
    block = tr["block"]
    for n in range(block, len(a) + 1, block):
        for f in (lambda j: len(j.prompt), lambda j: j.max_new):
            assert collections.Counter(map(f, a[:n])) == \
                collections.Counter(map(f, b[:n]))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_bounds_and_warmed(name):
    tr = _mix(name)
    jobs = make_jobs(tr, 151936, 5)
    pt, ot = tr["prompt_tokens"], tr["output_tokens"]
    for j in jobs:
        assert pt["min"] <= len(j.prompt) <= pt["max"]
        assert ot["min"] <= j.max_new <= ot["max"]
        assert len(j.prompt) + j.max_new <= tr["s_max"]
        assert j.prompt.min() >= 0 and j.prompt.max() < 151936
    # every prefill bucket the jobs reach is warmed
    bucket = lambda n: 1 << max(3, (n - 1).bit_length())
    assert {bucket(len(j.prompt)) for j in jobs} <= \
        {bucket(n) for n in warm_prompt_lengths(tr)}


def test_quantiles_follow_the_distribution():
    q = quantile_lengths(dict(median=256, sigma=0.5, min=1, max=10**6), 101)
    assert q[50] == 256 and list(q) == sorted(q)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@dataclasses.dataclass
class _Req:
    rid: int
    prompt: object
    max_new_tokens: int
    temperature: float
    on_token: object
    out_tokens: list = dataclasses.field(default_factory=list)
    outcome: object = None


class _Engine:
    """Admits whatever fits and emits one token a row per ``step``, each
    step taking ``dt`` on the clock: the engine's contract, no model."""

    def __init__(self, slots, clock, dt=0.04):
        self.slots, self.clock, self.dt = slots, clock, dt
        self.queue, self.active = [], []

    def submit(self, req):
        self.queue.append(req)

    def _emit(self, r):
        r.out_tokens.append(0)
        r.on_token(r, 0)
        if len(r.out_tokens) >= r.max_new_tokens:
            r.outcome = "completed"
            self.active.remove(r)

    def pump(self):
        n = 0
        while self.queue and len(self.active) < self.slots:
            r = self.queue.pop(0)
            self.active.append(r)
            self._emit(r)
            n += 1
        return n

    def step(self):
        self.clock.t += self.dt
        for r in list(self.active):
            self._emit(r)


def test_preroll_staggers_the_first_requests():
    tr = _mix("closed-decode")
    n = tr["clients"]
    jobs = make_jobs(tr, 151936, 3)[:n]
    cut = [staggered(j, c, n).max_new / j.max_new for c, j in enumerate(jobs)]
    for c, (f, j) in enumerate(zip(cut, jobs)):
        assert abs(f - (c + 0.5) / n) <= 0.5 / j.max_new + 1e-9


def test_closed_window_opens_on_a_steady_loop():
    """Admissions come at about the steady rate from the window's first
    step: none of the clients' requests started together."""
    tr = _mix("closed-decode")
    clock = _Clock()
    gen = LoadGen(_Engine(tr["slots"], clock), _Req, clock)
    opened = []
    win = gen.closed(make_jobs(tr, 151936, 3), tr["clients"], 12.0,
                     tr["preroll_steps"], lambda: opened.append(clock.t))
    assert opened == [win.t0] and win.t0 > 0
    sent = [r.sent - win.t0 for r in win.recs if r.sent >= win.t0]
    quarters = collections.Counter(int(4 * s / 12.0) for s in sent)
    assert min(quarters[q] for q in range(4)) >= \
        max(quarters.values()) / 3, quarters
