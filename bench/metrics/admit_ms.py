"""admit_ms: host time of one admission in the serve engine's loop
(``ServeEngine._prefill_into``: prefill call, cache merge, first-token
sync), from the engine's own counters over the window."""


def read(ctx):
    st = ctx["stats"]
    n = st.get("admitted", 0)
    return st["prefill_s"] / n * 1e3 if n else None
