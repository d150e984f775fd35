"""Share of the pass-1 vertex slots that are cap padding, weighted by
slots, over the windows launched in the measured window (the program's
plan census)."""


def read(run):
    used = slots = 0
    for plan in run.record.get("plans", []):
        for m in plan.metas:
            if m.shape is not None:
                used += m.n_vertices
                slots += m.vertex_cap
    return 100.0 * (1.0 - used / slots) if slots else None
