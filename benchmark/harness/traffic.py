"""The one traffic generator: a mix is a data file under
``benchmark/traffic/`` and this module turns ``(mix, vocab, seed, seconds)``
into requests.  Pure: the same arguments give the same requests, byte for
byte.

Copied in idea from ``paddle_tpu/serving/loadgen.py`` (lognormal / Zipf
lengths, Poisson and on/off arrivals, Zipf tenants with shared prefixes),
with two changes.  Arrivals are in SECONDS on the wall clock, so a slow
engine is offered the same load as a fast one.  And the SCHEDULE — which
length arrives when, for which tenant, sampled or greedy — belongs to the
mix, not to the seed: lengths and gaps are the quantiles of their
distribution at (i + 0.5) / n in an order drawn once from the mix's own
constant, like a recorded trace that every run replays.  The seed draws the
token ids (and the weights).  In an open loop the order of arrivals decides
which request waits behind which long prompt, and with it the tail of time
to first token: over six orders that tail read 1.4 to 3.3 s, over two runs
of one order it moved by 0.3 to 2.6 % (PERF.md section 2).

A mix file:

    {"loop": "open" | "backlog",
     "rate_per_s": 3.8,                  # open: mean arrivals a second
     "arrival": "poisson" | "bursty",    # open only
     "burst_on_s": 2.0, "burst_off_s": 6.0,   # bursty: on/off windows; the
                                         # rate inside a burst is scaled so
                                         # the mean stays rate_per_s
     "backlog_requests_per_s": 2.0,      # backlog: requests made per second
     "ramp_allow_s": 60,                 # of (ramp_allow_s + window), due at 0
     "window_opens": {"after_s": 25},    # or {"after_retired": 24}
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.6,
                "min": 32, "max": 1024},
     "output": {... same keys ...},
     "tenants": 1, "tenant_zipf_a": 1.2, "shared_prefix_len": 0,
     "greedy_share": 1.0, "temperature": 0.7}   # the rest sample

``dist`` may also be "zipf" with "buckets" and "zipf_a", or "fixed" with
"value".
"""

import dataclasses
import math
from statistics import NormalDist

import numpy as np

SCHEDULE = 0x7A11           # the one order of every mix's lengths and gaps


@dataclasses.dataclass
class TrafficRequest:
    index: int
    due_s: float                # seconds after the generator's time 0
    tenant: int
    prompt: np.ndarray          # (plen,) int32, tenant prefix included
    max_new_tokens: int
    temperature: float          # 0 = greedy


def _quantile_points(n):
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def length_set(n, spec):
    """The n lengths of one distribution: its quantiles at (i + 0.5)/n,
    rounded and clamped — the same set for every seed."""
    dist = spec.get("dist", "lognormal")
    u = _quantile_points(n)
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif dist == "zipf":
        buckets = np.asarray(spec["buckets"], np.float64)
        p = np.arange(1, len(buckets) + 1, dtype=np.float64) \
            ** -float(spec["zipf_a"])
        cdf = np.cumsum(p / p.sum())
        vals = buckets[np.minimum(np.searchsorted(cdf, u), len(buckets) - 1)]
    elif dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = int(spec.get("min", 1))
    hi = int(spec.get("max", 1 << 30))
    return np.clip(np.round(vals).astype(np.int64), lo, hi)


def _arrival_times(n, mix, rng):
    """Due times in seconds of an open loop: exponential gaps at their
    quantiles (mean 1 / rate), in the seed's order; ``bursty`` packs them
    into on windows separated by silent off windows, the mean rate kept."""
    rate = float(mix["rate_per_s"])
    gaps = -np.log1p(-_quantile_points(n)) / rate
    gaps = gaps * (n / rate) / gaps.sum()        # mean gap exactly 1/rate
    rng.shuffle(gaps)
    kind = mix.get("arrival", "poisson")
    if kind == "poisson":
        return np.cumsum(gaps)
    if kind != "bursty":
        raise ValueError(f"unknown arrival process {kind!r}")
    on, off = float(mix["burst_on_s"]), float(mix["burst_off_s"])
    t_on = np.cumsum(gaps) * on / (on + off)     # time spent inside bursts
    return t_on + np.floor(t_on / on) * off


def _shuffled_lengths(n, spec, rng):
    lengths = length_set(n, spec)
    rng.shuffle(lengths)
    return lengths


def _phase(mix, vocab, rng, tokens, horizon_s, t_from, first_index):
    """The requests of one phase: its own whole set of lengths and gaps in
    the order ``rng`` (the mix's) gives, token ids from ``tokens`` (the
    seed's)."""
    loop = mix["loop"]
    if loop == "open":
        n = max(1, int(round(float(mix["rate_per_s"]) * horizon_s)))
        due = t_from + _arrival_times(n, mix, rng)
    elif loop == "backlog":
        n = max(1, int(math.ceil(
            float(mix["backlog_requests_per_s"]) * horizon_s)))
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {loop!r}")
    plens = _shuffled_lengths(n, mix["prompt"], rng)
    olens = _shuffled_lengths(n, mix["output"], rng)
    tenants = int(mix.get("tenants", 1))
    plen_shared = int(mix.get("shared_prefix_len", 0))
    ranks = np.arange(1, tenants + 1, dtype=np.float64)
    p = ranks ** -float(mix.get("tenant_zipf_a", 1.2))
    who = rng.choice(tenants, n, p=p / p.sum())
    prefixes = [np.random.default_rng([0x5EED, t]).integers(
        1, vocab, plen_shared).astype(np.int32) for t in range(tenants)]
    greedy = np.ones(n, bool)
    share = float(mix.get("greedy_share", 1.0))
    if share < 1.0:
        greedy[: int(round((1.0 - share) * n))] = False
        rng.shuffle(greedy)
    out = []
    for i in range(n):
        own = max(1, int(plens[i]) - plen_shared)
        prompt = np.concatenate(
            [prefixes[who[i]],
             tokens.integers(1, vocab, own).astype(np.int32)])
        out.append(TrafficRequest(
            index=first_index + i, due_s=float(due[i]), tenant=int(who[i]),
            prompt=prompt, max_new_tokens=int(olens[i]),
            temperature=0.0 if greedy[i]
            else float(mix.get("temperature", 0.7))))
    return out


def generate(mix, vocab, seed, seconds):
    """The requests of one run with a window of ``seconds``.  An ``open``
    loop gets two phases, each with the whole set of its own lengths and
    gaps: the ramp (``window_opens.after_s`` seconds of arrivals) and then
    the window, so that the window holds the whole set.  A ``backlog`` gets
    ``backlog_requests_per_s * (ramp_allow_s + seconds)`` requests, all due
    at 0.  The schedule is the same for every seed; the token ids are the
    seed's."""
    rng = np.random.default_rng(SCHEDULE)
    tokens = np.random.default_rng([int(seed), 0x7A11])
    if mix["loop"] == "backlog":
        return _phase(mix, vocab, rng, tokens,
                      seconds + float(mix.get("ramp_allow_s", 0.0)), 0.0, 0)
    ramp_s = float(mix.get("window_opens", {}).get("after_s", 0.0))
    ramp = (_phase(mix, vocab, rng, tokens, ramp_s, 0.0, 0)
            if ramp_s > 0 else [])
    return ramp + _phase(mix, vocab, rng, tokens, seconds, ramp_s, len(ramp))
