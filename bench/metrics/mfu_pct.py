"""Model FLOP/s over the window (2 x nonzero weights each processed
token passes through, its top-k experts only, plus attention's score and
value products at its context; bench/counters.py) over the chips' peak
bf16 FLOP/s."""


def read(run):
    w = run.win
    if not run.peak or w.flops <= 0:
        return None
    return 100.0 * w.flops / (w.seconds * run.device["count"]
                              * run.peak["bf16_flops"])
