"""Device time of the sketch-pass Pallas kernel events per engine call,
in ms, from the trace. Nothing found: no value."""


def read(ctx):
    t, w = ctx.trace, ctx.window
    if t is None or not w.engine_calls:
        return None
    fams = {c["family"] for c in w.counters.get("calls", [])}
    total = sum(t.family_s.get(f, 0.0) for f in fams)
    return total / w.engine_calls * 1e3 if total > 0 else None
