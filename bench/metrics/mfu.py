"""Model operations of the window's rounds over (window x the chip's
bf16 peak), in percent.  Operations are counted from the logged rounds
(``harness.flops.round_flops``: trained lanes, measured devices, pairs
estimated, the combine) at the client's costs (``run.costs``, from its
reference file), with nothing recomputed counted."""


def read(run):
    if not run.rounds or not run.peaks or not run.costs \
            or run.seconds <= 0:
        return None
    return 100.0 * run.model_flops() / (run.seconds * run.peaks["bf16_flops"])
