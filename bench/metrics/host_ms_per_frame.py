"""Window time outside the planner, NPU-call and flush spans, per frame
answered: the serving loop's own host work (dispatch, resizes, syncs)."""


def read(run):
    if not run.answered:
        return None
    inside = sum(run.spans.total(n) for n in ("plan", "npu_call", "edge_flush"))
    return 1e3 * (run.window_s - inside) / run.answered
