import numpy as np

from benchmark.harness import traffic

MIX = {"loop": "open", "arrival": "poisson", "rate_per_s": 5.0,
       "prompt": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                  "min": 4, "max": 512},
       "output": {"dist": "zipf", "buckets": [4, 16, 64], "zipf_a": 1.2},
       "tenants": 3, "shared_prefix_len": 8, "greedy_share": 0.5}


def _bytes(reqs):
    return b"".join(r.prompt.tobytes() + np.float64(r.due_s).tobytes()
                    + np.int64([r.max_new_tokens, r.tenant]).tobytes()
                    + np.float64(r.temperature).tobytes() for r in reqs)


def test_same_spec_and_seed_give_the_same_bytes():
    a = traffic.generate(MIX, 1000, 2**31 + 5, 20.0)
    b = traffic.generate(MIX, 1000, 2**31 + 5, 20.0)
    assert _bytes(a) == _bytes(b)
    assert len(a) == 100


def test_seeds_differ_in_tokens_not_in_the_schedule():
    a = traffic.generate(MIX, 1000, 1, 20.0)
    b = traffic.generate(MIX, 1000, 2, 20.0)
    assert _bytes(a) != _bytes(b)
    for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
                  lambda r: r.due_s, lambda r: r.tenant,
                  lambda r: r.temperature):
        assert list(map(field, a)) == list(map(field, b))
    # the lengths are the quantiles of their distribution, shuffled
    plens = [len(r.prompt) for r in a]
    assert sorted(plens) == [max(9, int(n)) for n in      # prefix 8 + >= 1
                             traffic.length_set(100, MIX["prompt"])]
    assert plens != sorted(plens)
    # exponential gaps at their quantiles: the last arrival falls at n / rate
    assert abs(a[-1].due_s - 20.0) < 1e-9
    assert sum(r.temperature == 0 for r in a) == 50


def test_shared_prefix_and_bounds():
    reqs = traffic.generate(MIX, 1000, 3, 20.0)
    by_tenant = {}
    for r in reqs:
        by_tenant.setdefault(r.tenant, []).append(r.prompt[:8].tobytes())
        assert 8 < len(r.prompt) <= 512 and r.prompt.min() >= 1
    assert all(len(set(v)) == 1 for v in by_tenant.values())


def test_backlog_is_all_due_at_zero_and_bursty_keeps_the_rate():
    back = dict(MIX, loop="backlog", backlog_requests_per_s=2.0)
    assert {r.due_s for r in traffic.generate(back, 1000, 1, 10.0)} == {0.0}
    burst = dict(MIX, arrival="bursty", burst_on_s=2.0, burst_off_s=6.0)
    due = [r.due_s for r in traffic.generate(burst, 1000, 1, 80.0)]
    assert len(due) == 400 and 70.0 < due[-1] <= 80.0 + 1e-9
    assert all(t % 8.0 <= 2.0 + 1e-9 for t in due)
