"""``bitmap_spmm`` grid steps one decode step runs, over the packed stack
and the packed head (the engine's ``weight_stream_report``).  None where
the program does not count them."""


def read(run):
    return run.weight_stream.get("spmm_grid_steps_per_step")
