"""Device idle share of the training window's traced stretch: 1 - the
union of device-op intervals over the stretch, mean over the chips used."""


def read(ctx):
    red = ctx.get("reduced")
    if not red or red["window_s"] <= 0 or "updates_per_s" not in ctx["run"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
