"""What decides ``correct`` for a served model: the tokens the timed window
served, held against the plain reference.

For a sample of the requests the window finished (drawn from the seed, the
longest always in it) the reference runs once over prompt plus served tokens
and, at each served position, reads how far the served token's logit lies
below the reference's best.  Greedy tokens only: a sampled token may lie
anywhere.  Two numbers are compared, each with a limit from the cell's file:

    served_gap_max   the widest such gap over all positions compared
    served_gap_mean  their mean (steady from seed to seed)

``control_bits`` also reads, at the same positions, the gap of the token
that the reference computed with int-rounded weights puts first — the
control that has to come out as not correct (``benchmark/control.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import llama_arch

PAD_LEAST = 512             # the reference's query block


def sample_requests(finished, k, seed):
    """``k`` of the finished records, drawn from the seed, the longest
    (prompt + output) always among them; greedy ones only."""
    greedy = [r for r in finished if r["temperature"] == 0.0 and r["tokens"]]
    if not greedy:
        return []
    greedy.sort(key=lambda r: r["index"])
    longest = max(greedy, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in greedy if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    pick = list(rng.choice(len(rest), min(k - 1, len(rest)), replace=False))
    return [longest] + [rest[i] for i in sorted(pick)]


@jax.jit
def _gap_below_best(ref, tok):
    """Per row, the best logit minus the logit of ``tok``."""
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(
        ref, tok[:, None], axis=-1)[:, 0]


def served_gaps(weights, cfg, prompt, tokens, control_bits=None):
    """Per served position, reference-best logit minus the served token's
    logit (float32 numpy, >= 0); with ``control_bits`` also the same for the
    token the int-rounded reference puts first."""
    p, t = len(prompt), len(tokens)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(tokens, np.int32)])
    # padded to a power of two (under a causal mask a tail changes nothing
    # before it): a cell compiles four reference programs at most and every
    # later run finds them in the persistent cache
    padded = max(PAD_LEAST, 1 << (len(full) - 2).bit_length())
    ids = np.zeros(padded, np.int32)
    ids[:len(full) - 1] = full[:-1]
    nxt = np.zeros(padded, np.int32)
    nxt[:len(full) - 1] = full[1:]
    served = slice(p - 1, p - 1 + t)
    ref = llama_arch.logits(weights, cfg, ids)
    gaps = np.asarray(_gap_below_best(ref, jnp.asarray(nxt)))[served]
    if control_bits is None:
        return gaps, None
    low = llama_arch.logits(weights, cfg, ids, weight_bits=control_bits)
    first = jnp.argmax(low, axis=-1).astype(jnp.int32)
    return gaps, np.asarray(_gap_below_best(ref, first))[served]


def judge(gaps, limits):
    """The compared numbers beside their limits: [{"name", "value",
    "limit", "ok"}]."""
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    rows = []
    for name, value in (("served_gap_max", float(g.max()) if g.size else None),
                        ("served_gap_mean",
                         float(g.mean()) if g.size else None)):
        limit = float(limits[name])
        rows.append({"name": name, "value": value, "limit": limit,
                     "ok": value is not None and value <= limit})
    return rows
