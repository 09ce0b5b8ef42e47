"""Share of the rows' forwards that unmask nothing: over the window's
``serving.decode`` spans in the tracer's ring, the sum of their ``commits``
arg (rows whose block went in mask-free: the forward writes the K/V later
blocks read and delivers nothing) over the sum of their ``slots`` arg (every
live row's forward).  It is what folding the commit into the next block's
first denoising forward would be worth: at four denoising forwards a block,
one forward in five.  None against a program whose spans carry no such
args."""

from benchmark.harness import manifest as mf


def read(run):
    steps = mf.load_metric("diffusion.tokens_per_forward").block_steps(run)
    forwards = sum(a["slots"] for a in steps) if steps else 0
    if not forwards:
        return None
    return 100.0 * sum(a["commits"] for a in steps) / forwards
