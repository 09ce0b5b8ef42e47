"""Tokens a row's forward delivers, over the window: the sum of the
``delivered`` arg of the window's ``serving.decode`` spans in the tracer's
ring (tokens that left a block that came back mask-free, cut at
``max_new_tokens``) over the sum of their ``slots`` arg (the live rows of
each block step: every one a forward over its block, denoising or commit).
A block of four that takes four denoising forwards and a commit reads 0.8;
each position a forward unmasks beyond one, and a commit folded into the
next block's first forward, raise it.  None against a program whose spans
carry no such args (a model that does not generate by diffusion over
blocks)."""

from benchmark.harness import engine_spans


def block_steps(run):
    """The window's block steps' span args, or None where there is no ring
    to read or a span lacks the block tick's args."""
    ticks = engine_spans.ring_spans(run, "serving.decode")
    if not ticks or any("delivered" not in a for _, a in ticks):
        return None
    return [a for _, a in ticks]


def read(run):
    steps = block_steps(run)
    forwards = sum(a["slots"] for a in steps) if steps else 0
    if not forwards:
        return None
    return sum(a["delivered"] for a in steps) / forwards
