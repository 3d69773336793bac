"""Padding rows over all rows the edge forward ran (``BatchStats``)."""


def read(run):
    rows = run.edge_frames + run.edge_padded
    return run.edge_padded / rows if rows else None
