"""experts_hit_share: the share of the routed experts that the decode
steps reached, in percent: the serving stack's counters
``moe_experts_hit`` (distinct experts reached, summed over decode steps
and expert layers) over ``moe_layer_steps`` (expert layers run, summed
over decode steps) times the configuration's ``n_routed_experts``, over
the window.  It is the share of the routed experts' weight bytes that a
step must read.  A stack without the counters reads nothing."""


def read(ctx):
    st = ctx["stats"]
    n = st.get("moe_layer_steps", 0)
    experts = ctx["cj"].get("n_routed_experts")
    if not n or not experts or "moe_experts_hit" not in st:
        return None
    return st["moe_experts_hit"] / (n * experts) * 100.0
