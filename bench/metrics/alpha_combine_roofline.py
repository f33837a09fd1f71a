"""The Pallas alpha_combine kernel's share of its roofline, in percent:
per call, the larger of (operations / peak bf16 FLOP/s) and (bytes /
peak HBM bytes/s), over the kernel's device time in the trace.  The
call's shape is S = T = the padded device count and V = the client's
``transfer_width`` (``run.costs``, from its reference file); operations
and bytes come from ``harness.flops.alpha_combine_cost``.  At the sync
cells' sizes the bytes bound it."""
from harness.flops import alpha_combine_cost

#: the kernel's HLO instruction name in the trace's ops (``alpha_combine_
#: flat.<n>``; ops that only read its output are named otherwise)
KERNEL = "alpha_combine"


def read(run):
    if run.profile is None or not run.peaks or not run.costs:
        return None
    ns, calls = run.profile.time_of(lambda name: name.startswith(KERNEL))
    if not calls or ns <= 0:
        return None
    n = run.sim["devices"]
    mesh = max(int(run.sim.get("mesh", 0)), 1)
    n += -n % mesh
    ops, nbytes = alpha_combine_cost(n, n, run.costs["transfer_width"])
    floor_s = max(ops / run.peaks["bf16_flops"],
                  nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s * calls / (ns / 1e9)
