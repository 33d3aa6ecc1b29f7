"""step_host_ms: the host's own time per engine step, in ms: the
engine's ``step`` span less the spans in which the host waits on the
device (``sync``, the decode step's tokens back; ``first_token``, an
admission's first token back), over the window, from the engine's span
counters (``serve/spans.py``)."""


def read(ctx):
    st = ctx["stats"]
    n = st.get("step_n", 0)
    if not n or "sync_s" not in st or "first_token_s" not in st:
        return None
    return (st["step_s"] - st["sync_s"] - st["first_token_s"]) / n * 1e3
