"""Share of the rows the grouped expert products compute in one decode
step that carry a routed (token, expert) pair, in percent (the engine's
``weight_stream_report``).  None where the program does not count it or
the model has no experts."""


def read(run):
    return run.weight_stream.get("expert_row_use_pct")
