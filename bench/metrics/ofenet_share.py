"""Share of the traced stretch's busy device time spent in OFENet: the ops
inside the ``repro.ofenet`` scope (its feature passes for acting, targets
and losses, and its auxiliary step; the kind's ``scopes``, per chip) over
the busy time. None where the trace carries no scopes."""


def read(ctx):
    red = ctx.get("reduced")
    if not red or red["busy_s"] <= 0:
        return None
    t = red.get("scopes", {}).get("repro.ofenet", 0.0)
    if t <= 0:
        return None
    return 100.0 * t / red["busy_s"]
