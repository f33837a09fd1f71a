"""The accuracy sweep after the transfer (one forward per sample of
every active device at the client's ``eval_sample``, from the
configuration's reference file) over the program's ``eval`` phase's
seconds x the chip's bf16 peak, in percent.  None without the phase or
the costs."""


def read(run):
    if not run.rounds or not run.peaks or not run.costs:
        return None
    seconds = run.phase_total("eval")
    if seconds <= 0:
        return None
    ops = sum(r["row"]["n_active"] * run.sim["samples_per_device"]
              * run.costs["eval_sample"] for r in run.rounds)
    return 100.0 * ops / (seconds * run.peaks["bf16_flops"])
