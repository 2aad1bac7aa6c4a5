"""Modeled weight bytes one decode step streams from HBM, packed stack
plus LM head (the engine's ``weight_stream_report``), in MB."""


def read(run):
    b = run.weight_stream.get("sparse_bytes_per_step")
    return b / 1e6 if b else None
