"""The open-loop generator times each request from when it was due, so a
stall is charged to the requests behind it; checked under a fake clock."""

from benchmark.drivers import open_show
from benchmark.stats import percentile


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Done:
    """A future already settled, calling back at once."""

    def __init__(self, value):
        self.value = value

    def result(self, timeout=None):
        return self.value

    def add_done_callback(self, fn):
        fn(self)


def test_stall_is_charged_to_the_later_requests():
    clock = FakeClock()
    due = [0.0, 0.1, 0.2, 0.3, 0.4]

    def submit(k):
        if k == 1:
            clock.t += 1.0  # the submit of request 1 stalls for a second
        return Done(True)

    loop = open_show.OpenLoop(submit, due, clock=clock, sleep=clock.sleep)
    loop.run(100.0)
    lat = loop.latencies(100.0, horizon=1e9)
    # request 1 completes after its own stall; 2..4 are submitted late,
    # and their latency counts from their due times, not their submits
    assert lat[0] == 0.0
    assert abs(lat[1] - 1.0) < 1e-9
    assert [round(x, 9) for x in lat[2:]] == [0.9, 0.8, 0.7]
    assert [round(x, 9) for x in loop.late] == [0.0, 0.0, 0.9, 0.8, 0.7]


def test_refused_and_unanswered_requests_take_the_whole_wait():
    clock = FakeClock()

    class Never:
        def add_done_callback(self, fn):
            pass

    def submit(k):
        if k == 0:
            raise RuntimeError("overloaded")
        return Never() if k == 1 else Done(True)

    loop = open_show.OpenLoop(submit, [0.0, 0.5, 1.0], clock=clock,
                              sleep=clock.sleep)
    loop.run(100.0)
    lat = loop.latencies(100.0, horizon=160.0)
    assert lat == [60.0, 59.5, 0.0]
    assert loop.outcome[0][0] is False and loop.outcome[1] is None


def test_arrivals_are_the_same_gaps_for_every_seed():
    a = open_show.arrivals(100, 5, seed=1)
    b = open_show.arrivals(100, 5, seed=2**33 + 5)
    assert a != b
    assert len(a) == len(b) == 500
    assert all(0 <= t < 5 for t in a) and a[0] == 0.0
    gaps = lambda xs: sorted(round(y - x, 9) for x, y in zip(xs, xs[1:]))
    assert gaps(a + [5.0]) == gaps(b + [5.0])
    assert open_show.arrivals(100, 5, seed=1) == a


def test_nearest_rank_percentile():
    assert percentile([], 95) is None
    assert percentile([3.0], 95) == 3.0
    assert percentile(list(range(1, 101)), 95) == 95
