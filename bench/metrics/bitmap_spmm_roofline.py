"""``bitmap_spmm``'s share of its roofline: the least time the chip needs
for the sparse projections' work in the traced window (bench/counters.py:
for each call the larger of operations over peak FLOP/s and bytes over
peak HBM bandwidth), over the summed device time of the trace's
``bitmap_spmm`` operations, over the tensors the engine packed.  The
work of each decode and prefill call in the window is counted at its
live rows, as the harness saw them: the slots that decode, and the
prompt positions the prefill call writes; never the program's padding.
A prefill call ends before the LM head, so its work leaves the head out."""
from bench import counters


def read(run):
    r = run.reduction
    if not r or not r.kernels.get("bitmap_spmm"):
        return None
    c, packed = run.calls, run.kernel_tensors
    least = sum(counters.calls_least_time(
        run.model, run.sparsity, rows, kernel, run.peak)["least_s"]
        for rows, kernel in ((c.decode_rows, packed),
                             (c.prefill_rows, packed - {("lm", "head")})))
    return 100.0 * least / r.kernels["bitmap_spmm"]
